"""step_mfu.chat: Step vs the chip: model operations of the live rows
of each decode step, over the host time from the step before to it,
times the chip's bf16 peak (%); steps after idle time are left out of
both."""

from benchlib import costs as C


def read(run):
    ctxs = run.step_contexts()
    flops = secs = 0.0
    for step, gap in run.stall_gaps():
        flops += sum(C.token_flops(run.terms, run.model, run.n_layers, c)
                     for c in ctxs.get(step, []))
        secs += gap
    if not secs:
        return None
    return 100.0 * flops / (secs * run.peaks["bf16_flops"])
