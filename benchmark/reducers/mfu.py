"""Model FLOP/s utilisation of training: tokens per second per chip of
this run, times the operations a token needs forward and backward
(``flops.train_flops_per_token``; recomputation does not count), over
the chip's peak."""
from benchmark import flops


def reduce(meas):
    rate = meas["end_to_end"].get("train_tok_s_chip")
    if rate is None or not meas.get("peak"):
        return None
    per_token = flops.train_flops_per_token(meas["model"],
                                            meas["train"]["seq"])
    return 100.0 * rate * per_token / meas["peak"]["bf16_flops_per_s"]
