"""The decode steps' model operations (``bench.flops.decode_step_flops``)
over the traced window, the chips and their bf16 peak."""


def read(run):
    return 100.0 * run.counters["step_flops"] / (
        run.trace.window_s * run.chips * run.peaks["bf16_flops_per_s"])
