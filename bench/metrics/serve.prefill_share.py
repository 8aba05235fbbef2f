"""Share of the device's busy time spent in the admission program
(``ServeEngine``'s ``_admit``: the prefill of each admission wave)."""
ADMIT = "_admit"


def read(summary, job, out):
    runs = summary.program_s(ADMIT)
    if not runs or summary.busy_s <= 0:
        return None
    return 100.0 * sum(runs) / (summary.busy_s * summary.chips)
