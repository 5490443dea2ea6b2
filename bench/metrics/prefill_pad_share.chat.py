"""prefill_pad_share.chat: Scheduler: the share of the row-positions
the window's prefill and extend calls computed that were padding, from
the program's counters: 1 - prefill_tokens / prefill_positions.  None
where the record carries no ``prefill_positions``."""


def read(run):
    positions = run.counters.get("prefill_positions")
    if not positions:
        return None
    return 1.0 - run.counters["prefill_tokens"] / positions
