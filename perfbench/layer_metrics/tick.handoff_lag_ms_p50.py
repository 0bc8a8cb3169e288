"""Median time from the end of a tick's run on the device to the end of
the ``pt:step/drain`` that brought its tokens to the host; ticks are laid
against the device's runs by ``_program_trace.align_ticks``."""
from perfbench import loader, yardstick


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = pt.doc_of(run)
    lags = pt.handoff_lags_ms(doc)
    if not lags:
        return None
    run.setdefault("notes", []).append(
        pt.alignment_note(doc) + "; handoff lag p50 / p95 / max "
        + " / ".join(f"{x:.3f}" for x in (
            yardstick.percentile(lags, 50), yardstick.percentile(lags, 95),
            max(lags))) + f" ms of {len(lags)} drains")
    return yardstick.percentile(lags, 50)
