"""Model FLOP/s utilization: closed-form forward + backward FLOPs per
token (``benchmarks/lib/closed_forms.py``) times the tokens per second of
the traced stretch, over the chips' bf16 peak."""

LAYER = "device step"
UNIT = "%"
MOVES = "train_tok_s"
SOURCE = "device_trace"


def read(record):
    trace, f = record.get("trace"), record["facts"]
    if trace is None or not f["windows_traced"]:
        return None
    tokens = f["windows_traced"] * f["tokens_per_window"]
    achieved = f["flops_per_token"]["total"] * tokens / trace["window_s"]
    peak = record["peaks"]["bf16_flops_per_s"] * record["chips"]
    return 100.0 * achieved / peak
