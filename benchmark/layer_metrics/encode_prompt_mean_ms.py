"""Text towers: host milliseconds a prompt takes through the program's
``encode_prompt`` (tokenizer, the towers' jitted forward passes, the copies
to the host), from ``batchsched_hop_ms_total["encode_prompt"]`` over its
count.  The prompts are encoded before the window opens (each session's
claim), so this reads the counters as they stand when it opens, which is
since the rehearsal's reset: the sessions' own prompts, and nothing of the
window.  A program without the counter reads nothing, as ``hop_counters``."""


def read(ctx):
    try:
        at_open = ctx.result.counters_open
        n = at_open["batchsched_hop_count"]["encode_prompt"]
        ms = at_open["batchsched_hop_ms_total"]["encode_prompt"]
    except (KeyError, TypeError):
        return None
    return ms / n if n else None
