"""mfu.batch: Step vs the chip: useful model operations in the window
(the prompt tokens each admission computed, prefix hits and padded rows
left out, plus every output token the host held by the close) over
window x bf16 peak (%).  Needs the prefix hit of each request, which
traced runs record."""

from benchlib import costs as C


def read(run):
    if run.matched is None:
        return None
    t, m, L = run.terms, run.model, run.n_layers
    end = run.window_end
    flops = 0.0
    for r in run.requests:
        if not r.admitted:
            continue
        hit = run.matched.get(r.rid, 0)
        flops += sum(C.token_flops(t, m, L, pos + 1, head=False)
                     for pos in range(hit, r.prompt_len))
        flops += 2 * m["d_model"] * m["vocab_size"]      # first token
        for k in range(1, r.n_out):
            if r.token_times[k] <= end:
                flops += C.token_flops(t, m, L, r.prompt_len + k)
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops"])
