"""How far the residual mappings stand from doubly stochastic: the
largest ``|row sum - 1|`` or ``|column sum - 1|`` any ``H_res`` has shown
in a decode step since the engine was built, over the steps' rows (free
slots too) and sub-blocks — the running maximum the decode step keeps on
the device (``gpt.MHC_RES_DEV_VAR``), read once after the window
(``DecodeEngine.mhc_res_deviation``). Twenty Sinkhorn rounds in float32
leave about 1e-5 to 1e-3 on seeded weights; a kernel of fewer rounds, or
mappings computed in a narrower precision, would move it. ``None`` for a
program without the reading."""

LAYER = "model step on the device"
UNIT = "abs"
MOVES = "serve_tok_s"
SOURCE = "program_counter"


def read(record):
    return (record.get("counters") or {}).get("mhc_res_dev")
