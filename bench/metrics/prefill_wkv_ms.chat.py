"""prefill_wkv_ms.chat: Model step: device time (ms) per execution of
the prefill program spent in operations under the model's named scope
``wkv`` (the recurrence of the rwkv6 time mix), by self time, from the
trace.  None where the trace carries no scopes."""


def read(run):
    if run.trace is None or "scopes" not in run.trace:
        return None
    calls = run.trace["modules"].get("_prefill_fn", [])
    if not calls:
        return None
    wkv = run.trace["scopes"].get("_prefill_fn", {}).get("wkv", 0.0)
    return 1e3 * wkv / len(calls)
