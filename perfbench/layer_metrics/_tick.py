"""Shared by the readers that count the serving tick's runs: the device
time of each run of the tick's program, from the trace's line of program
runs (``jit_tick(<fingerprint>)`` on ``XLA Modules``)."""
import statistics

from perfbench import tracered

MODULE_LINES = ("XLA Modules",)


def tick_runs_ms(doc) -> list:
    return [ev["dur_ns"] / 1e6 for p in tracered.device_planes(doc)
            for ln in p["lines"] if ln["name"] in MODULE_LINES
            for ev in ln["events"] if "tick" in ev["name"].lower()]


def device_ms_p50(run):
    doc = run["ctx"].trace_doc
    runs = tick_runs_ms(doc) if doc is not None else []
    return statistics.median(runs) if runs else None
