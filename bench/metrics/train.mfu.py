"""Model FLOPs of the training steps run in the traced window (forward and
backward, recomputation not counted) over the window's length times the
chips times the chip's bf16 peak: the whole step's share of the peak, idle
time included."""
from bench import counting

STEP = "train_step"


def read(summary, job, out):
    runs = summary.program_s(STEP)
    if not runs:
        return None
    mix = job.cell.mix
    flops = counting.train_flops(job.cell.config, mix["batch"],
                                 mix["seq_len"]) * len(runs) / summary.chips
    peak = job.peaks["bf16_flops"]
    return 100.0 * flops / (summary.window_s * summary.chips * peak)
