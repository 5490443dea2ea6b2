"""device_idle_share.chat: Device: 1 - (union of device operation
intervals) / traced window, from the trace."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 1.0 - run.trace["busy_s"] / run.trace["window_s"]
