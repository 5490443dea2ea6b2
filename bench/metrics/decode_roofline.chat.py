"""decode_roofline.chat: Step vs the chip: the roofline's least time of
the traced decode steps (weights read once, cache or state of live rows
only, operations of live rows only) over their measured device time (%)."""

from benchlib import costs as C


def read(run):
    if run.trace is None:
        return None
    ctxs = run.step_contexts()
    least = spent = 0.0
    for call in run.trace["decode_calls"]:
        if call["step"] in ctxs:
            f, b = C.decode_step_cost(run.terms, run.model, run.n_layers,
                                      ctxs[call["step"]])
            least += C.least_seconds(f, b, run.peaks)[0]
            spent += call["seconds"]
    return 100.0 * least / spent if spent else None
