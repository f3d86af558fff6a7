"""Share of the neighbor-list slots that hold a neighbor (%): the
program's ``nbr_live_slots`` over ``nbr_slots``, summed over the builds
the window's chunks stepped with. Nothing to read where the program has
no such counters."""

from bench import scopes


def read(record):
    return scopes.layer_metrics(record["trace"], record["steps"],
                                record["counters"])["nbr_slot_fill"]
