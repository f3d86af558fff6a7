"""How the program's kernels are named in a device trace."""

import re

# The dp_fused pallas_calls pass no name=; on the chip their custom calls
# are named after the kernels, fused_fwd.N and fused_bwd.N. Traces of the
# kernels that formed G (tests/bench/fixtures/trace_v5e_cu16k.json) name
# them after their jitted wrappers under autodiff, jvp_jit_fused_fwd__.8
# and transpose_jvp_jit_fused_bwd___.26; the pattern takes both.
DP_FUSED = re.compile(r"(^|_)fused_(fwd|bwd)(_|\.|$)")


def is_dp_fused(name: str) -> bool:
    return DP_FUSED.search(name) is not None
