"""Model FLOP/s utilisation: 6 N FLOPs per token (N parameters, no
recomputed or attention FLOPs) times the window's tokens per second, over
the chips' bf16 peak (host clock)."""


def read(record, trace):
    if "model_flops_per_unit" not in record:
        return None
    flops = record["model_flops_per_unit"] * len(record["units"]) / record["window_s"]
    return 100.0 * flops / (record["peaks"]["bf16_flops"] * record["chips"])
