"""Share of the collectives' device time during which no other operation
ran on the same chip: what the step really waits for."""

from benchmarks.lib import xplane

LAYER = "SPMD engine"
UNIT = "%"
MOVES = "train_tok_s"
SOURCE = "device_trace"


def read(record):
    trace = record.get("trace")
    if trace is None:
        return None
    events = trace["ops"][min(trace["ops"])]
    coll = xplane.collectives(events) + xplane.collectives(
        trace["async_ops"].get(min(trace["ops"]), []))
    if not coll:
        return None
    others = [e for e in xplane.leaves(events)
              if not xplane.COLLECTIVE.match(e[3])]
    return 100.0 * xplane.exposed_seconds(coll, others) \
        / xplane.busy_seconds(coll)
