"""Median over the window's nine slices of Laguna's cell's prompt and output
tokens a second (``served.tokens_per_s_slice_p50``'s reader): what a hold of
the machine in one slice does not move."""


def read(run):
    return run["facts"].get("serve_tokens_per_s_slice_p50")
