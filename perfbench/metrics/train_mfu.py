"""whole step: analytic forward+backward FLOPs (3 x forward, 2 FLOPs a
multiply-add, harness/costs.py) x images a second of the traced window,
over chips x the chip's bf16 peak. Cannot pass 100."""
from perfbench.harness import costs


def read(facts):
    red = facts["trace"]
    steps = red.step_count(fallback=facts.get("window_steps"))
    if not steps:
        return None
    rate = steps * facts["batch"] / red.window_s
    flops = costs.resnet_train_flops_per_image(facts["config"])
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops * rate / peak
