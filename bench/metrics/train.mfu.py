"""Model FLOPs utilisation of the train step: model FLOPs per token
(forward and backward, recomputation not counted) times tokens per
second, over the chips' bf16 peak."""


def read(run):
    r = run["record"]
    return 100.0 * r["flops_per_token"] * r["tokens_per_s"] / (
        run["device"]["count"] * run["peaks"]["bf16_flops_per_s"])
