"""decode_ms.batch: Model step: device time per execution of the
decode program (ms), from the trace."""


def read(run):
    return run.mean_device_ms("_decode_fn")
