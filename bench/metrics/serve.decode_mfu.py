"""Model FLOPs of the decode chunks run in the traced window (the tokens
each live request produced, each over its own cached length) over their
device time times the chip's bf16 peak."""
from bench import counting

DECODE = "_decode"


def read(summary, job, out):
    runs = summary.program_s(DECODE)
    chunks = out.records.get("decode_chunks") or []
    if not runs or not chunks:
        return None
    c = job.cell.config
    flops = sum(counting.decode_flops(c, lens)
                for lens in counting.decode_steps(chunks))
    peak = job.peaks["bf16_flops"]
    return 100.0 * flops / (sum(runs) * peak)
