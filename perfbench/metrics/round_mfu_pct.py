"""Round step: model FLOP utilization of the whole round, in %.

Model FLOPs per cell-round, counted here from the configuration's shapes
(a multiply-add counts 2; convolutions and matrix products only), times
the traced run's cell-rounds per second, over chips x the chip's bf16
peak. The program runs its products at the TPU's default precision, one
bfloat16 pass, so the bf16 peak is the ceiling.

Per cell-round: 3 x forward FLOPs per sample (forward and backward) x N
devices x batch x local steps, plus the eval's forward FLOPs over the
test set on the rounds that evaluate."""
import numpy as np


def forward_flops(config) -> int:
    """Forward FLOPs of one sample."""
    if config["task"] == "logreg":
        return 2 * int(np.prod(config["input_shape"])) * config["n_classes"]
    h, w, c_prev = config["input_shape"]
    k = config["kernel_size"]
    flops = 0
    for i, c in enumerate(config["conv_channels"]):
        flops += 2 * h * w * k * k * c_prev * c  # SAME padding, stride 1
        c_prev = c
        if i in (1, 2):  # 2x2 max pools after the second and third convs
            h, w = h // 2, w // 2
    flops += 2 * c_prev * config["hidden"] + 2 * config["hidden"] * config["n_classes"]
    return flops


def flops_per_cell_round(config, traffic) -> float:
    f = forward_flops(config)
    train = 3 * f * config["n_devices"] * config["batch_size"] * config["local_steps"]
    t = np.arange(traffic["rounds"])
    evals = np.sum((t % traffic["eval_every"] == 0) | (t == traffic["rounds"] - 1))
    return train + f * config["n_test"] * evals / traffic["rounds"]


def read(ctx):
    from perfbench.peaks import peaks

    peak = peaks(ctx.device_kind)["bf16_flops"]
    return 100.0 * flops_per_cell_round(ctx.config, ctx.traffic) * ctx.rate / (ctx.chips * peak)
