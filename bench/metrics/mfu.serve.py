"""Whole step: the model FLOPs of the requests served in the window (each
prompt's prefill, then one decode step per further token; padding rows and
masked keys not counted), over the window's seconds, as a percent of the
chip's bf16 peak."""


def read(data):
    peaks, flops = data.get("peaks"), data.get("model_flops")
    if not peaks or not flops or not data.get("requests"):
        return None
    return 100.0 * flops / data["window_s"] / peaks["bf16_flops_per_s"]
