"""Model FLOP/s utilisation of training a sparse decoder: ``mfu`` with
the operations of the parameters a token is routed through
(``flops_moe.train_flops_per_token``: attention, router, ``moe_top_k``
experts a layer, head), over the chip's peak."""
from benchmark import flops_moe


def reduce(meas):
    rate = meas["end_to_end"].get("train_tok_s_chip")
    if rate is None or not meas.get("peak"):
        return None
    per_token = flops_moe.train_flops_per_token(meas["model"],
                                                meas["train"]["seq"])
    return 100.0 * rate * per_token / meas["peak"]["bf16_flops_per_s"]
