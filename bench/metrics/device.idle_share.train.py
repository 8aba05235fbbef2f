"""Share of the traced window in which no operation ran on the device,
while training (averaged over the chips)."""


def read(summary, job, out):
    return 100.0 * summary.idle_share
