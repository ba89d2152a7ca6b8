"""The straggler's width: the most experts WITH weights any one token of
a decode step chose, over the expert branches, since the engine was
built — the running maximum the decode step keeps on the device beside
its identity pairs (``DecodeEngine.zero_pairs``, column 1; free slots'
rows count too). Between 0 and ``expert_top_k``: with identity experts
the work of a token varies, and in a deployment the token that chose the
most experts with weights is the one an expert-parallel layer waits for.
``None`` where the engine has no such tally."""

LAYER = "expert routing"
UNIT = "count"
MOVES = "req_tok_ms_p50"
SOURCE = "program_counter"


def read(record):
    return (record.get("counters") or {}).get("real_experts_max")
