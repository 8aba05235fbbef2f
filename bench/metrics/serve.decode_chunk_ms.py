"""Mean device time of one decode-chunk program (``_decode``: ``chunk``
steps of ``decode_n`` over every slot)."""
DECODE = "_decode"


def read(summary, job, out):
    runs = summary.program_s(DECODE)
    if not runs:
        return None
    return 1e3 * sum(runs) / len(runs)
