"""``pulls_per_wave`` under the batch traffic: the serve loop's blocking
device-to-host reads a wave (``ServeReport.host_pulls`` over
``waves``).  None on a report without the counter, as from a program
that predates it."""


def read(w):
    pulls = getattr(w.report, "host_pulls", None)
    if pulls is None or not w.report.waves:
        return None
    return pulls / w.report.waves
