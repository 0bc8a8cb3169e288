"""Prompt tokens made resident in warm-in over the time from the first chunk to
the last slot's first token (the engine's own ``chunk`` and ``first_token``
events; ``families/ling3_serve.warm_prefill``): the chunk path's only reading
in a cell whose window is all decode (the chunked per-channel rule from a
carried state, MLA over a chunk row, a chunk of 256 beside the decode rows
of the slots already filled). Not judged."""


def read(run):
    value = run["facts"].get("warm_prefill_tokens_per_s")
    return None if value is None else 1.0 * value
