"""prefill_ms.chat: Model step: device time per execution of the
prefill program (ms), from the trace."""


def read(run):
    return run.mean_device_ms("_prefill_fn")
