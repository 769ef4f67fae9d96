"""Model FLOP/s utilization of the traced run: img/s x (forward + backward
FLOPs per image, from the symbol's shapes) over chips x the bf16 peak. An
end-to-end utilization, not a kernel's roofline share."""
from benchmark import flops


def read(obs):
    if obs["kind"] != "fit" or not obs.get("peak"):
        return None
    f = obs["fit"]
    return flops.mfu(f["img_per_s"], f["flops_per_img"], obs["chips"],
                     obs["peak"]["bf16_flops_per_s"])
