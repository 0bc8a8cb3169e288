"""One entry a quantity among the five newer backlog cells (PR 48; Ling's
cell since PR 53, Falcon-H1's since PR 56): a folded reader returns in each
cell, to the last digit, what that cell's retired copy returned (``dots3.*``,
``dsv2.*``, ``olmoh.*``, the ``.longdoc``, ``.dsv2`` and ``.olmoh`` suffixes;
``ling.*`` and ``kda.prep_ms_per_tick``; ``fh1.*``, ``ssd.*``, ``gdn.*`` and
``kda.step_*``, a recurrent state's three passes under one name whatever the
rule). The copies' bodies are spelt out here against the cell's own trace
helper, on each cell's synthetic tick and on every piece of a real trace
recorded under ``recorded_served/``; a tick that names none of the five
mechanisms (the recorded GPT tick) reads nothing, and the long-prompt cell's
three readers of facts alone are cases against a GPT run's facts. The
helpers are pinned from below: the accepted ones must be there and exactly
one must answer a tick, and a helper a later PR brings beside them fails
nothing. The two sparse
training cells share ``moe.train_mfu_pct`` and ``moe.experts_roofline_pct``
the same way (``solar2.train_mfu_pct`` and ``moe.held_experts_roofline_pct``
were Solar-Open2's copies)."""
import json
import os
import types

import pytest

import shutil
import sys

from perfbench import loader, yardstick, yardstick_gdn, yardstick_kda, \
    yardstick_ling3, yardstick_mla, yardstick_mla_dense, yardstick_moe, \
    yardstick_ssd

import test_pb_dots3 as dots3
import test_pb_dsv2 as dsv2
import test_pb_falcon_h1 as falcon
import test_pb_ling3 as ling3
import test_pb_olmo_hybrid as olmoh
import test_pb_olmoe as olmoe
import test_pb_solar_open2 as solar

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "recorded_served")


def _helper(name):
    return loader.load_module("layer_metrics", name)


def _part(part):
    return lambda tr, run: tr.read_part(run, part)


def _fact(key, scale=1.0):
    def read(tr, run):
        value = run["facts"].get(key)
        return None if value is None else scale * value
    return read


def _device_ms_p50(tr, run):
    if tr.parts_ms(run) is None:
        return None
    return _helper("_tick").device_ms_p50(run)


def _prefill_tokens(tr, run):
    f = run["facts"]
    return f["prefill_rows_per_tick"] * f["prefill_chunk"]


def _host_ms(tr, run):
    pt = _helper("_program_trace")
    return pt.host_ms_per_tick(pt.doc_of(run))


def _hbm_pct(tick_bytes):
    """A retired ``*.tick_hbm_roofline_pct``: the family's bytes a tick at
    the chip's peak over the tick's time."""
    def read(tr, run):
        s = tr.tick_shape(run)
        moved = tick_bytes(run["ctx"].config, s)
        return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / s["ms"]
    return read


def _mfu_pct(tick_flops):
    """A retired ``*.tick_mfu_pct``: the family's operations a tick over
    the tick's time and the chip's peak."""
    def read(tr, run):
        s = tr.tick_shape(run)
        ops = tick_flops(run["ctx"].config, s)
        return 100.0 * ops / (s["ms"] * 1e-3) / s["peak"].bf16_flops
    return read


def _roofline(part, y, least):
    """A retired ``*_roofline_pct`` of one part: the reader spelt the
    family's helper, its own word for the part and its yardstick ``y`` out:
    ``least(y, config, tick_shape)`` gives ``(operations, bytes)``."""
    def read(tr, run):
        s, ms = tr.tick_shape(run), tr.read_part(run, part)
        return 100.0 * y.least_ms(*least(y, run["ctx"].config, s),
                                  s["peak"]) / ms
    return read


def _step(y, c, s):
    return y.step_flops(c, s["live"]), y.step_bytes(c, s["live"])


def _chunk(y, c, s):
    return (y.chunk_flops(c, s["chunk"]),
            y.chunk_bytes(c, s["chunk"], s["chunk_rows"]))


def _attention(y, c, s):
    return (y.attention_flops(c, s["decode_keys"] + s["chunk_pairs"]),
            y.attention_bytes(c, s["decode_keys"] + s["chunk_keys"]))


def _ling_decode_roofline(tr, run):
    """``ling.mla_decode_roofline_pct`` as it stood until PR 56: it named
    ``_ling3_trace`` and ``yardstick_ling3``; the helper hands the floor out
    since (``least_ms``) and the reader asks ``_served``."""
    s, ms = tr.tick_shape(run), tr.read_part(run, "mla_decode")
    return 100.0 * yardstick_ling3.attention_least_ms(
        run["ctx"].config, (s["decode"],), s["peak"]) / ms


def _experts_hbm(experts_bytes):
    def read(tr, run):
        s, ms = tr.tick_shape(run), tr.read_part(run, "experts")
        least = experts_bytes(run["ctx"].config, s["touched"]) \
            / s["peak"].hbm_bytes_per_s * 1e3
        return 100.0 * least / ms
    return read


def _ling_experts_hbm(tr, run):
    """``ling.moe_experts_hbm_roofline_pct`` as it was spelt: the same
    quantity with the hundred multiplied in first, so it may differ from
    the folded reader in the last place (on the chip's traced run it did:
    81.11235670896026 beside 81.11235670896025, PR 53)."""
    s, ms = tr.tick_shape(run), tr.read_part(run, "experts")
    moved = yardstick_ling3.experts_bytes(run["ctx"].config, s["touched"])
    return 100.0 * moved / s["peak"].hbm_bytes_per_s * 1e3 / ms


#: what every cell's copies did alike: folded name -> the copy's body
SHARED = {
    "served.tick_device_ms_p50": _device_ms_p50,
    "served.dense_ms_per_tick": _part("dense"),
    "served.head_sample_ms_per_tick": _part("head_sample"),
    "served.unscoped_ms_per_tick": _part("unscoped"),
    "served.prefill_tokens_per_tick": _prefill_tokens,
    "served.decode_rows_per_tick": _fact("decode_rows_per_tick"),
    "served.tokens_per_s_slice_p50": _fact("serve_tokens_per_s_slice_p50"),
    "served.host_ms_per_tick": _host_ms,
}
#: the two latent-attention cells' shared names
LATENT = {
    "latent.scatter_ms_per_tick": _part("scatter"),
    "moe.tick_route_ms_per_tick": _part("route"),
    "moe.tick_experts_ms_per_tick": _part("experts"),
    "moe.tick_shared_ms_per_tick": _part("shared"),
    "moe.tick_expert_load_max_over_mean":
        _fact("tick_expert_load_max_over_mean"),
    "moe.tick_experts_touched_pct":
        _fact("tick_experts_touched_share", 100.0),
    "pool.live_latent_pct": _fact("live_kv_share", 100.0),
}
#: what the cells that hold a share of an expert-parallel layer under a group
#: limit count besides (DeepSeek-V2's own entry until Ling's cell joined it)
GROUPS = {"moe.tick_group_hit_pct": _fact("tick_group_hit_share", 100.0)}
def state(own, y, chunk=True, attn=True):
    """The copies of a cell that keeps a recurrent state a slot: ``gdn.*``
    (Olmo-Hybrid), ``kda.step_*`` and ``gdn.prep`` (Ling), ``ssd.*`` and
    ``fh1.attn_*`` (Falcon-H1), each against the helper's ``own`` words for
    the passes and the family's yardstick ``y``."""
    out = {"state.step_ms_per_tick": _part(own + "_step"),
           "state.step_hbm_roofline_pct": _roofline(own + "_step", y, _step),
           "state.prep_ms_per_tick": _part(
               "gdn_prep" if own == "kda" else own + "_prep"),
           "pool.live_state_slots_pct": _fact("live_state_share", 100.0)}
    if chunk:
        out.update({
            "state.chunk_ms_per_tick": _part(own + "_chunk"),
            "state.chunk_roofline_pct": _roofline(own + "_chunk", y, _chunk)})
    if attn:
        out.update({
            "attn.full_ms_per_tick": _part("attn"),
            "attn.full_roofline_pct": _roofline("attn", y, _attention)})
    return out


#: the decode rows' dense latent attention (DeepSeek-V2's own until PR 53)
DENSE_MLA = {"mla.dense_decode_ms_per_tick": _part("mla_decode")}
CELLS = {
    "serve-dots3-longdoc-backlog": dict(
        helper="_dots3_trace", test=dots3,
        kernels=("%moe_gmm.3 = custom-call",),
        copies={**SHARED, **LATENT,
                "served.tick_hbm_roofline_pct": _hbm_pct(
                    lambda c, s: yardstick_mla.tick_bytes(
                        c, s["decode"], s["chunks"], s["chunk"],
                        s["context"], s["sampled"], s["touched"])),
                "served.tick_mfu_pct": _mfu_pct(
                    lambda c, s: yardstick_mla.tick_flops(
                        c, s["decode"], s["chunks"], s["chunk"],
                        s["context"], s["sampled"], s["expert_rows"])),
                "moe.tick_experts_hbm_roofline_pct": _experts_hbm(
                    yardstick_mla.experts_bytes)}),
    "serve-dsv2-docqa-backlog": dict(
        helper="_dsv2_trace", test=dsv2,
        kernels=("%moe_gmm.3 = custom-call",),
        copies={**SHARED, **LATENT, **GROUPS, **DENSE_MLA,
                "served.tick_hbm_roofline_pct": _hbm_pct(
                    lambda c, s: yardstick_mla_dense.tick_bytes(
                        c, s["tokens"], (s["decode"], s["chunk"]),
                        s["sampled"], s["touched"])),
                "served.tick_mfu_pct": _mfu_pct(
                    lambda c, s: yardstick_mla_dense.tick_flops(
                        c, s["tokens"], (s["decode"], s["chunk"]),
                        s["sampled"], s["expert_rows"])),
                "moe.tick_experts_hbm_roofline_pct": _experts_hbm(
                    yardstick_mla_dense.experts_bytes)}),
    "serve-olmo-hybrid-gen-backlog": dict(
        helper="_olmoh_trace", test=olmoh, kernels=None,
        copies={**SHARED, **state("gdn", yardstick_gdn),
                "served.tick_hbm_roofline_pct": _hbm_pct(
                    yardstick_gdn.tick_bytes),
                "served.tick_mfu_pct": _mfu_pct(yardstick_gdn.tick_flops)}),
    # the bodies of ``ling.*`` and ``kda.prep_ms_per_tick`` (PR 49), which
    # asked ``_ling3_trace`` directly; its window is all decode, so it lists
    # no ``served.prefill_tokens_per_tick``
    "serve-ling3-longgen-backlog": dict(
        helper="_ling3_trace", test=ling3, kernels=None,
        copies={**{k: v for k, v in SHARED.items()
                   if k != "served.prefill_tokens_per_tick"},
                **LATENT, **GROUPS, **DENSE_MLA,
                **state("kda", yardstick_ling3, chunk=False, attn=False),
                "served.tick_hbm_roofline_pct": _hbm_pct(
                    yardstick_ling3.tick_bytes),
                "served.tick_mfu_pct": _mfu_pct(yardstick_ling3.tick_flops),
                "moe.tick_experts_hbm_roofline_pct": _ling_experts_hbm,
                "ling.mla_decode_roofline_pct": _ling_decode_roofline}),
    # the bodies of ``fh1.*`` and ``ssd.*`` (PR 54), which asked
    # ``_falcon_h1_trace`` directly (``needs``, its own words for the parts)
    "serve-falcon-h1-gen-backlog": dict(
        helper="_falcon_h1_trace", test=falcon, kernels=None,
        copies={**SHARED, **state("ssd", yardstick_ssd),
                "pool.live_kv_pct.backlog": _fact("live_kv_share", 100.0),
                "served.tick_hbm_roofline_pct": _hbm_pct(
                    yardstick_ssd.tick_bytes),
                "served.tick_mfu_pct": _mfu_pct(yardstick_ssd.tick_flops)}),
}
#: the one copy whose arithmetic ran in another order than its folded reader's
LAST_PLACE = {("serve-ling3-longgen-backlog",
               "moe.tick_experts_hbm_roofline_pct")}


def same(cell, name, got, want):
    """Equal to the last digit; for ``LAST_PLACE`` within two units of it."""
    if (cell, name) in LAST_PLACE:
        return got == pytest.approx(want, rel=4.5e-16, abs=0)
    return got == want
CASES = [(cell, name) for cell, case in CELLS.items()
         for name in case["copies"]]
TRACE_READERS = sorted(
    name for name in {n for case in CELLS.values() for n in case["copies"]}
    if name not in ("served.prefill_tokens_per_tick",
                    "served.decode_rows_per_tick",
                    "served.tokens_per_s_slice_p50",
                    "moe.tick_expert_load_max_over_mean",
                    "moe.tick_experts_touched_pct", "pool.live_latent_pct",
                    "moe.tick_group_hit_pct", "pool.live_state_slots_pct",
                    "pool.live_kv_pct.backlog"))


def _host(doc):
    """The engine's spans of two ticks, for the host's reader."""
    events = []
    for tick in range(2):
        t0 = tick * 70_000_000
        for i, name in enumerate(("admit", "build", "dispatch")):
            events.append({"name": f"pt:step/{name}", "start_ns": t0 + i * 100,
                           "dur_ns": 400_000, "stats": {"tick": tick}})
    doc["planes"].append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": events}]})
    return doc


def _synthetic_run(cell, monkeypatch):
    case = CELLS[cell]
    t = case["test"]
    doc = t._synthetic(t.SCOPES) if case["kernels"] is None \
        else t._synthetic(t.SCOPES, kernels=case["kernels"])
    run, pt = t._run_with(_host(doc), t.real_config(), dict(t.FACTS))
    monkeypatch.setattr(pt, "load", lambda: doc)
    return run


@pytest.mark.parametrize("cell,name", CASES)
def test_a_folded_reader_returns_what_the_cells_copy_returned(
        cell, name, monkeypatch):
    run = _synthetic_run(cell, monkeypatch)
    want = CELLS[cell]["copies"][name](_helper(CELLS[cell]["helper"]), run)
    assert want is not None and want > 0, name
    assert same(cell, name,
                loader.load_module("layer_metrics", name).read(run), want)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_own_helper_and_no_other_reads_its_tick(cell, monkeypatch):
    """``_served`` names no family: it finds the helpers by listing its
    directory, and the run's tick picks the one that reads it."""
    run = _synthetic_run(cell, monkeypatch)
    served = _helper("_served")
    assert served.trace_of(run) is _helper(CELLS[cell]["helper"])
    # the accepted helpers are there; a later PR may bring one more
    assert set(served.helpers()) >= {c["helper"] for c in CELLS.values()}
    assert [n for n in served.helpers()
            if _helper(n).parts_ms(run) is not None] \
        == [CELLS[cell]["helper"]]
    # ... and the trace was cut for that helper alone: the others looked
    # for their mechanism's scope and found none (``names_scope``)
    pt = _helper("_program_trace")
    assert pt.cuts_of(pt.doc_of(run)) == [CELLS[cell]["helper"]]
    with open(served.__file__, encoding="utf-8") as f:
        source = f.read()
    for name in FAMILY_WORDS:
        assert name not in source.split('"""', 2)[2], name


#: what a reader that names no family does not spell: the five families'
#: helpers and every yardstick (``_program_trace``, ``_tick``, ``_holds`` and
#: ``_served`` are no family's)
FAMILY_WORDS = ("dots3", "dsv2", "olmoh", "ling", "falcon", "yardstick")
SHARED_PREFIXES = ("served.", "state.", "attn.full_", "moe.tick_", "latent.",
                   "pool.")


def _shared_names():
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    return sorted(m["name"] for m in bench["per_layer"]
                  if m["name"].startswith(SHARED_PREFIXES))


@pytest.mark.parametrize("name", _shared_names())
def test_a_shared_reader_names_no_family_and_no_yardstick(name):
    """A reader an entry of several cells names asks ``_served`` (or
    ``_program_trace``, ``_tick``, ``_holds``, the run's facts): the code
    under its docstring spells no family's helper and no yardstick, so a
    later family's cell joins its list and edits no reader."""
    path = os.path.join(loader.HERE, "layer_metrics", name + ".py")
    with open(path, encoding="utf-8") as f:
        code = f.read().split('"""', 2)[2]
    for word in FAMILY_WORDS + ("_trace",):
        assert word not in code.replace("_program_trace", ""), (name, word)


def test_a_sixth_helper_beside_the_five_fails_nothing(tmp_path, monkeypatch):
    """The door a later ``model_config`` PR comes through: a file
    ``_<family>_trace.py`` in the served form (``tick_needs``, ``least_ms``,
    the shared part names). ``_served.helpers()`` lists it, it answers for
    none of the accepted ticks, and a tick that names its mechanism is read
    through the shared readers with no reader edited."""
    dst, real = str(tmp_path / "perfbench"), loader.HERE
    shutil.copytree(real, dst, ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(dst, "layer_metrics", "_sixth_trace.py"), "w",
              encoding="utf-8") as f:
        f.write(SIXTH)
    loader.HERE = dst
    kept = {name: sys.modules.pop(name) for name in list(sys.modules)
            if name.startswith("perfbench.layer_metrics.")}
    try:
        served = _helper("_served")             # the copy's, loaded anew
        assert served.helpers() == sorted(
            [c["helper"] for c in CELLS.values()] + ["_sixth_trace"])
        for cell in CELLS:                    # none of theirs is its tick
            run = _synthetic_run(cell, monkeypatch)
            assert served.trace_of(run).__name__.endswith(
                CELLS[cell]["helper"])
            assert _helper("_sixth_trace").parts_ms(run) is None
        t = falcon                            # a tick of the sixth rule
        doc = t._synthetic(["blk/xyz/step", "blk/ffn", "tick/head"])
        run, pt = t._run_with(_host(doc), t.real_config(), dict(t.FACTS))
        monkeypatch.setattr(pt, "load", lambda: doc)
        assert served.trace_of(run) is _helper("_sixth_trace")
        read = lambda n: loader.load_module("layer_metrics", n).read(run)
        assert read("state.step_ms_per_tick") == 2.0
        assert read("state.step_hbm_roofline_pct") == 100.0 * 1.5 / 2.0
        assert read("state.chunk_roofline_pct") is None   # no floor: nothing
        assert read("served.dense_ms_per_tick") == 2.0
        assert read("served.tick_hbm_roofline_pct") > 0
        assert pt.cuts_of(doc) == ["_sixth_trace"]
    finally:
        loader.HERE = real
        for name, mod in list(sys.modules.items()):
            if name.startswith("perfbench.") and (getattr(
                    mod, "__file__", None) or "").startswith(dst):
                del sys.modules[name]
        sys.modules.update(kept)


SIXTH = '''"""A served family's helper as a later PR would bring it."""
import re

from perfbench import loader, yardstick

_PART = {"blk/xyz/step": "xyz_step", "blk/ffn": "dense",
         "tick/head": "head_sample"}
_SCOPE = re.compile(r"\\b(" + "|".join(
    re.escape(n) for n in sorted(_PART, key=len, reverse=True)) + r")\\b")
ORDER = ("dense", "xyz_step", "head_sample", "unscoped")
MECHANISM = ("blk/xyz/step",)
SHARED = {"state_step": "xyz_step"}


def part(ev):
    found = _SCOPE.findall(ev.get("scope", ""))
    return _PART[found[-1]] if found else "unscoped"


def parts_ms(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    doc = pt.doc_of(run)
    if doc is None:
        return None

    def compute():
        if not pt.names_scope(doc, _SCOPE, MECHANISM):
            return None
        parts = pt.parts_ms(doc, "tick", part, ORDER)
        n = parts.pop("n_runs")
        return {k: v / n for k, v in parts.items()}

    return pt._once(doc, "sixth parts", compute)


def read_part(run, name):
    parts = parts_ms(run)
    return None if parts is None else parts.get(SHARED.get(name, name), 0.0)


def tick_shape(run):
    if parts_ms(run) is None:
        return None
    return {"ms": loader.load_module("layer_metrics",
                                     "_tick").device_ms_p50(run),
            "peak": yardstick.chip_peak("TPU v5 lite")}


def tick_needs(run):
    s = tick_shape(run)
    return None if s is None else (s, 1e9, 1e12)


def least_ms(run, part):
    return 1.5 if part == "state_step" and parts_ms(run) is not None else None
'''


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_benchmark_json_lists_the_cell_under_every_folded_name(cell):
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    for name in CELLS[cell]["copies"]:
        assert cell in lists[name], name
    for name in lists:                    # and under no copy's name
        assert not name.startswith(("dots3.", "dsv2.", "olmoh.")) \
            and not name.endswith((".longdoc", ".dsv2", ".olmoh")), name
        assert not name.startswith("ling.") or name in (
            "ling.warm_prefill_tokens_per_s",   # the chunk path, warm-in's
            "ling.mla_decode_roofline_pct"), name   # the decode rows alone
    assert "kda.prep_ms_per_tick" not in lists
    # PR 56: Falcon-H1's copies, the three rules' names for one state's
    # passes and the long-prompt cell's three fact readers
    for name in lists:
        assert not name.startswith(("fh1.", "ssd.", "gdn.", "kda.step_")), \
            name
    assert not {"sched.prefill_tokens_per_tick", "sched.decode_rows_per_tick",
                "sched.serve_tokens_per_s_slice_p50"} & set(lists)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_folded_reader_finds_nothing_in_a_tick_that_names_no_mechanism(
        name, monkeypatch):
    """The recorded tick is a served GPT's (``blk/attn``, ``blk/ffn``):
    none of the five helpers reads it, so no folded reader does, and none
    of them cut the trace to say so."""
    pt = _helper("_program_trace")
    doc = pt.load_recorded(os.path.join(HERE,
                                        "recorded_scoped_tick.json.gz"))
    ctx = types.SimpleNamespace(
        trace_doc=doc, config=loader.load_json(loader.root_file(
            "perfbench/configs/gpt3-1.3b-serve.json")),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    run = {"ctx": ctx, "notes": [], "facts": {
        "decode_rows_per_tick": 9.0, "prefill_rows_per_tick": 0.25,
        "prefill_chunk": 32, "live_kv_share": 0.5}}
    monkeypatch.setattr(pt, "load", lambda: doc)
    assert _helper("_served").trace_of(run) is None
    assert pt.cuts_of(doc) == []
    if name == "served.host_ms_per_tick":     # the host's spans are there
        assert loader.load_module("layer_metrics", name).read(run) > 0
    else:
        assert loader.load_module("layer_metrics", name).read(run) is None


# --- the long-prompt cell's three readers of facts alone ------------------------
#: folded name -> (the retired ``sched.*`` copy's body, spelt out; a GPT
#: run's facts: the copies read no trace, so none is made)
LONGPROMPT = {
    "served.prefill_tokens_per_tick": lambda f: (
        f["prefill_rows_per_tick"] * f["prefill_chunk"]
        if "prefill_rows_per_tick" in f else None),
    "served.decode_rows_per_tick": lambda f: f.get("decode_rows_per_tick"),
    "served.tokens_per_s_slice_p50":
        lambda f: f.get("serve_tokens_per_s_slice_p50"),
}


@pytest.mark.parametrize("name", sorted(LONGPROMPT))
def test_the_long_prompt_cells_fact_readers_are_the_served_ones(name):
    """``sched.prefill_tokens_per_tick``, ``sched.decode_rows_per_tick`` and
    ``sched.serve_tokens_per_s_slice_p50`` were the ``served.*`` readers
    letter for letter under the docstring: the cell joins their lists."""
    facts = {"decode_rows_per_tick": 11.25, "prefill_rows_per_tick": 0.75,
             "prefill_chunk": 512, "serve_tokens_per_s_slice_p50": 5170.5}
    ctx = types.SimpleNamespace(trace_doc=None, config=loader.load_json(
        loader.root_file("perfbench/configs/gpt3-1.3b-serve.json")))
    read = loader.load_module("layer_metrics", name).read
    want = LONGPROMPT[name](facts)
    assert want is not None and read({"ctx": ctx, "facts": facts}) == want
    assert read({"ctx": ctx, "facts": {}}) is None      # nothing to read
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    assert "serve-longprompt-backlog" in lists[name]
    assert name.replace("served.", "sched.").replace(
        "tokens_per_s_slice", "serve_tokens_per_s_slice") not in lists


# --- the two sparse training cells' shared names --------------------------------
def _train_mfu(flops_per_token):
    """A retired ``*.train_mfu_pct``."""
    def read(run):
        f, ctx = run["facts"], run["ctx"]
        peak = yardstick.chip_peak(ctx.devices[0].device_kind).bf16_flops
        flops = flops_per_token(ctx.config, f["seq"])
        return 100.0 * f["tokens_per_s"] * flops / (len(ctx.devices) * peak)
    return read


def _experts_ms(run):
    return _helper("_moe_trace").read_part(run, "experts")


def _olmoe_experts_roofline(run):
    f, ctx = run["facts"], run["ctx"]
    peak = yardstick.chip_peak(ctx.devices[0].device_kind)
    return yardstick_moe.experts_roofline_pct(
        _experts_ms(run), f["micro"] * f["seq"], f["n_micro"], ctx.config,
        peak)


def _solar_experts_roofline(run):
    f, ctx = run["facts"], run["ctx"]
    peak = yardstick.chip_peak(ctx.devices[0].device_kind)
    return yardstick_kda.held_experts_roofline_pct(
        _experts_ms(run), f["moe_rows_held"], f["n_micro"], ctx.config, peak)


#: cell -> (its tests' module, the step's facts, folded name -> the body of
#: the reader the cell had: ``moe.*`` were OLMoE's, ``solar2.train_mfu_pct``
#: and ``moe.held_experts_roofline_pct`` Solar-Open2's)
TRAIN_CELLS = {
    "train-olmoe-1chip-4k": (
        olmoe, {"traced_steps": 1, "micro": 1, "seq": 4096, "n_micro": 8,
                "tokens_per_s": 30000.0},
        {"moe.train_mfu_pct": _train_mfu(
            yardstick_moe.olmoe_train_flops_per_token),
         "moe.experts_roofline_pct": _olmoe_experts_roofline}),
    "train-solar-open2-1chip": (
        solar, {"traced_steps": 1, "micro": 1, "seq": 8192, "n_micro": 2,
                "tokens_per_s": 12000.0, "moe_rows_held": 13104.0},
        {"moe.train_mfu_pct": _train_mfu(yardstick_kda.train_flops_per_token),
         "moe.experts_roofline_pct": _solar_experts_roofline}),
}


@pytest.mark.parametrize("cell,name", [
    (cell, name) for cell, case in TRAIN_CELLS.items() for name in case[2]])
def test_a_sparse_training_cells_shared_reader_returns_its_copys_number(
        cell, name, monkeypatch):
    t, facts, copies = TRAIN_CELLS[cell]
    pt = _helper("_program_trace")
    monkeypatch.setitem(pt._DOC, "doc", t.synthetic_doc())
    run = {"ctx": t.FakeCtx(t.real_config()), "facts": dict(facts)}
    want = copies[name](run)
    assert want is not None and want > 0
    assert loader.load_module("layer_metrics", name).read(run) == want
    bench = loader.load_json(loader.root_file("BENCHMARK.json"))
    lists = {m["name"]: m.get("workloads", ()) for m in bench["per_layer"]}
    assert cell in lists[name]
    assert "solar2.train_mfu_pct" not in lists
    assert "moe.held_experts_roofline_pct" not in lists


# --- pieces of real traces, recorded on the chip ------------------------------
def _recorded():
    if not os.path.isdir(RECORDED):
        return []
    return sorted(f[:-len(".json.gz")] for f in os.listdir(RECORDED)
                  if f.endswith(".json.gz"))


@pytest.mark.parametrize("cell", _recorded())
def test_a_recorded_piece_of_the_cells_trace_reads_the_same_both_ways(
        cell, monkeypatch):
    """``recorded_served/<cell>.json.gz``: some ticks of the cell's traced
    run on the chip in ``_program_trace``'s plain form, with the run's
    facts beside them (``<cell>.facts.json``): every folded reader against
    the copy's body, and every value a positive number."""
    pt = _helper("_program_trace")
    doc = pt.load_recorded(os.path.join(RECORDED, cell + ".json.gz"))
    with open(os.path.join(RECORDED, cell + ".facts.json"),
              encoding="utf-8") as f:
        facts = json.load(f)
    config = loader.load_json(loader.root_file(
        loader.load_cell(cell)["config_entry"]["file"]))
    ctx = types.SimpleNamespace(
        trace_doc=doc, config=config,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    run = {"ctx": ctx, "facts": facts, "notes": []}
    monkeypatch.setattr(pt, "load", lambda: doc)
    tr = _helper(CELLS[cell]["helper"])
    for name, copy in CELLS[cell]["copies"].items():
        want = copy(tr, run)
        assert want is not None and want > 0, name
        assert same(cell, name, loader.load_module(
            "layer_metrics", name).read(run), want), name
    for name in ("served.tick_hbm_roofline_pct", "served.tick_mfu_pct"):
        assert loader.load_module("layer_metrics", name).read(run) < 100
