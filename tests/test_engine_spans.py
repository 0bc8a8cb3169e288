"""The engine's host phases on the profiler's clock (ISSUE 24):
``profiler.trace.scope`` writes ``pt:`` annotations into any live
``jax.profiler`` session, with ids as stats; the serving engine's step is
cut into ``pt:step/*`` spans; drains are counted; and the readers a driver
needs are public."""
import glob
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import events, registry, trace
from paddle_tpu.serving import ServingConfig, ServingEngine

STEP_SPANS = ("step/drain", "step/admit", "step/chunks", "step/grow",
              "step/build", "step/dispatch")


def toy_engine(**kw):
    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64))
    net.eval()
    return ServingEngine(net, ServingConfig(num_slots=2, page_size=16, **kw))


def pt_events(log_dir):
    """``[(name, stats, start_ns)]`` of the ``pt:`` events of a session."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.SPAN_PREFIX):
                    out.append((ev.name[len(trace.SPAN_PREFIX):],
                                dict(ev.stats), ev.start_ns))
    return sorted(out, key=lambda e: e[2])


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Three engine steps and a submit inside a profiler session, with
    ``profiler.enable()`` off."""
    assert not trace.is_enabled()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    eng = toy_engine()
    eng.submit(np.arange(5, dtype=np.int32), 8)
    eng.step()                      # compiles outside the session
    recorded = len(trace.events())
    jax.profiler.start_trace(log_dir)
    try:
        eng.submit(np.arange(7, dtype=np.int32), 4)
        for _ in range(3):
            eng.step()
        eng.drain(0)
    finally:
        jax.profiler.stop_trace()
    return pt_events(log_dir), len(trace.events()) - recorded


@pytest.mark.parametrize("name", STEP_SPANS + ("submit/fold_key",))
def test_a_session_sees_the_span(session, name):
    spans, _ = session
    mine = [stats for n, stats, _ in spans if n == name]
    assert mine, sorted({n for n, _, _ in spans})
    if name.startswith("step/"):
        ticks = [s["tick"] for s in mine]
        assert ticks == sorted(ticks) and len(set(ticks)) >= 3, ticks


def test_spans_number_the_tick_they_work_for(session):
    spans, _ = session
    sent = [s["tick"] for n, s, _ in spans if n == "step/dispatch"]
    assert sent == list(range(sent[0], sent[0] + 3))
    for n, s, _ in spans:
        if n == "step/drain":
            assert s["waited"] in (0, 1) and s["tick"] <= sent[-1]
    # build and dispatch of one tick carry its number, in that order
    order = [n for n, s, _ in spans if s.get("tick") == sent[1]
             and n != "step/drain"]
    assert order == ["step/admit", "step/chunks", "step/grow", "step/build",
                     "step/dispatch"]


def test_nothing_is_recorded_in_memory_while_the_profiler_is_off(session):
    assert session[1] == 0


def test_scope_is_one_shared_no_op_without_a_session_or_enable():
    assert not trace.is_enabled()
    a, b = trace.scope("x", tick=1), trace.scope("y")
    assert a is b
    with a:
        pass
    assert trace.live_spans() == {}


def test_scope_records_and_annotates_while_enabled(tmp_path):
    trace.enable()
    try:
        with trace.scope("outer"):
            with trace.scope("inner", tick=3):
                time.sleep(0.001)
    finally:
        summary = trace.disable()
    assert summary["outer/inner"]["count"] == 1
    assert summary["outer"]["total_ms"] >= summary["outer/inner"]["total_ms"]
    trace.reset_events()


def test_drains_are_counted_and_timed_once_a_tick():
    reg = registry()
    before = {k: reg.counter(k).value for k in
              ("serving/drain_waited", "serving/drain_ready",
               "serving/ticks")}
    turn = reg.histogram("serving/tick_turnaround_ms")
    n0 = turn.count
    eng = toy_engine()
    eng.submit(np.arange(40, dtype=np.int32), 5)    # two chunks, then 4
    eng.run()
    d = {k: reg.counter(k).value - v for k, v in before.items()}
    # the first chunk's tick hands the host nothing and is not drained
    assert d["serving/ticks"] == 6
    assert d["serving/drain_waited"] + d["serving/drain_ready"] == 5
    assert turn.count - n0 == 5
    assert turn.percentile(50) > 0


def test_the_spec_ticks_inline_sync_counts_as_waited():
    from paddle_tpu.serving import SpecConfig

    reg = registry()
    before = reg.counter("serving/drain_waited").value
    ticks = reg.counter("serving/ticks").value
    paddle.seed(1)
    draft = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=1,
                          num_heads=2, max_seq_len=64))
    draft.eval()
    eng = toy_engine(spec=SpecConfig(draft, k=2))
    eng.submit(np.arange(5, dtype=np.int32), 4)
    eng.run()
    assert reg.counter("serving/drain_waited").value - before == \
        reg.counter("serving/ticks").value - ticks > 0


@pytest.mark.parametrize("gone", ["serving/token_syncs",
                                  "serving/decode_batch",
                                  "serving/tokens_per_sec"])
def test_a_counter_nobody_read_is_gone(gone):
    import paddle_tpu.profiler as profiler
    import paddle_tpu.serving as serving
    from paddle_tpu.serving import engine

    eng = toy_engine()
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.run()
    snap = registry().snapshot()
    assert all(gone not in section for section in snap.values()
               if isinstance(section, dict))
    for mod in (profiler, serving, engine):
        assert gone not in mod.__doc__


def test_tokens_so_far_reads_what_the_host_has_been_handed():
    eng = toy_engine()
    rid = eng.submit(np.arange(5, dtype=np.int32), 6)
    assert eng.tokens_so_far(rid) == ()
    seen = []
    while not eng.idle():
        eng.step() or eng.drain(0)
        seen.append(len(eng.tokens_so_far(rid)))
    assert seen == sorted(seen) and seen[-1] == 6
    assert list(eng.tokens_so_far(rid)) == list(eng.run()[rid])


def test_served_weights_are_what_the_tick_reads():
    from paddle_tpu.models.gpt import gpt_cached_apply

    eng = toy_engine()
    stacked, other = eng.served_weights()
    assert stacked["attn.qkv_proj.weight"].shape[0] == 2    # [L, ...]
    assert "embeddings.wte.weight" in other
    # a plain cached forward on them picks the token the engine emits
    prompt = np.arange(5, dtype=np.int32)
    rid = eng.submit(prompt, 1)
    (tok,) = eng.run()[rid]
    cfg = eng.model_config
    hd = cfg.hidden_size // cfg.num_heads
    zeros = np.zeros((1, cfg.num_layers, 8, cfg.num_heads, hd), np.float32)
    logits, _, _ = gpt_cached_apply(cfg, stacked, other, zeros, zeros,
                                    prompt[None], 0)
    assert int(np.argmax(np.asarray(logits)[0])) == tok


def test_submit_takes_the_due_time_for_ttft_and_says_how_late():
    eng = toy_engine()
    ttft = registry().histogram("serving/ttft_ms")
    n0, cursor = ttft.count, events.log().next_seq
    rid = eng.submit(np.arange(5, dtype=np.int32), 2,
                     due_t=time.perf_counter() - 5.0)
    eng.run()
    evs, _ = events.log().since(cursor)
    (sub,) = [e for e in evs if e.kind == "submit" and e.rid == rid]
    assert 5000.0 <= sub.attrs["late_ms"] < 5500.0
    assert ttft.count == n0 + 1 and ttft.snapshot()["max"] >= 5000.0
    # without a due time nothing is said of lateness
    cursor = events.log().next_seq
    rid = eng.submit(np.arange(5, dtype=np.int32), 1)
    evs, _ = events.log().since(cursor)
    assert "late_ms" not in [e for e in evs if e.kind == "submit"][0].attrs
