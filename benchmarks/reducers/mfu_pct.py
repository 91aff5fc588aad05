"""The whole step's share of the chip's peak: images of the window times the
forward FLOPs of one image (from shapes), over window seconds times the
peak. It still bounds a gain after a kernel is replaced."""


def reduce(reduced: dict, spec: dict):
    if not reduced["images"] or not reduced["window_s"]:
        return None
    flops = reduced["images"] * reduced["work"][spec["work"]]
    return 100.0 * flops / (reduced["window_s"]
                            * reduced["peaks"]["bf16_tflops"] * 1e12)
