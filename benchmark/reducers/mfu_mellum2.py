"""Model FLOP/s utilisation of training a stack of window and full
layers on a chip's share of the experts: ``mfu`` with the operations a
token needs here (``flops_mellum2.train_flops_per_token``: the window's
pairs on sliding layers, ``moe_top_k x held / n_experts`` experts a
token, the slice's head), over the chip's peak."""
from benchmark import flops_mellum2


def reduce(meas):
    rate = meas["end_to_end"].get("train_tok_s_chip")
    if rate is None or not meas.get("peak"):
        return None
    per_token = flops_mellum2.train_flops_per_token(meas["model"],
                                                    meas["train"]["seq"])
    return 100.0 * rate * per_token / meas["peak"]["bf16_flops_per_s"]
