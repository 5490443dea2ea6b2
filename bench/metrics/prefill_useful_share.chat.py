"""prefill_useful_share.chat: Scheduler: prompt tokens admitted over the
rows x bucket positions the prefill calls computed (every call prefills
max_batch rows)."""


def read(run):
    groups = run.admission_groups()
    if not groups:
        return None
    useful = sum(r.prompt_len for g in groups.values() for r in g)
    computed = sum(run.max_batch * run.bucket(max(r.prompt_len for r in g))
                   for g in groups.values())
    return useful / computed
