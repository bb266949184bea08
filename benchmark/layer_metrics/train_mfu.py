"""Model FLOPs per sample (benchmark/flops.py; recomputation not counted)
times samples per second of this run's whole window, over chips times the
bf16 peak of benchmark/peaks.json."""


def read(inputs):
    f, peaks = inputs["facts"], inputs["peaks"]
    if not peaks:
        return None
    return 100.0 * f["flops_per_sample"] * f["samples_per_s"] / (
        f["chips"] * peaks["bf16_flops_per_s"])
