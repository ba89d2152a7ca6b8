"""How late the load generator ran: submit time minus due time, 95th
percentile over the window's requests. A starved generator must not be
read as a fast server."""

from benchmarks.lib.stats import percentile

LAYER = "load generator"
UNIT = "ms"
MOVES = "req_tok_ms_p95"
SOURCE = "host_clock"


def read(record):
    return percentile(record["samples"]["gen_late_ms"], 95)
