"""AOT-lower the hybrid step for a REAL TPU topology and assert the
multi-chip bf16 path (VERDICT r3 weak #5 / next #7): on CPU meshes the
pipeline promotes bf16 collectives to f32 as an XLA:CPU-crash workaround
(pipeline.py boundary_f32), so the bf16 ppermute/psum code that runs on
actual TPU hardware was executed by nothing. jax.experimental.topologies
gives an offline v5e 2x4 compile target: the lowering below is the exact
program an 8-chip TPU mesh would run, and the HLO is inspected for
native-bf16 collective-permutes with no f32 promotion at the stage
boundary."""
import os
import re

import numpy as np
import pytest

import jax

pytestmark = pytest.mark.filterwarnings("ignore")


def _tpu_topology_devices():
    from jax.experimental import topologies

    last = None
    for attempt in range(2):
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x4")
            return topo.devices
        except Exception as e:
            last = e
            # a concurrently-crashed compile leaves a stale lockfile that
            # aborts libtpu init — clear it once and retry
            if "libtpu_lockfile" in str(e) and attempt == 0:
                try:
                    os.remove("/tmp/libtpu_lockfile")
                    continue
                except OSError:
                    pass
            break
    pytest.skip(f"TPU topology unavailable: {last}")


def _build_abstract_trainer(devices, dp, tp, pp, sp=1, remat_policy=None):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.distributed_strategy import \
        DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(0)
    # head_dim = 512/4 = 128 (lane-width aligned; Mosaic rejects the
    # sub-128 head dims that only the CPU interpret path tolerates)
    cfg = GPTConfig(vocab_size=512, hidden_size=512, num_layers=4,
                    num_heads=4, max_seq_len=128)
    with paddle.LazyGuard():
        model = GPT(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    s = DistributedStrategy()
    s.amp = True
    s.recompute = True
    s.hybrid_configs = {"dp_degree": dp, "mp_degree": tp, "pp_degree": pp,
                        "sp_degree": sp}
    mesh = create_mesh({"dp": dp, "tp": tp, "pp": pp, "sp": sp},
                       np.array(devices)[:dp * tp * pp * sp])
    return HybridPipelineTrainer(model, opt, s, mesh, n_micro=4,
                                 param_dtype="bfloat16",
                                 moment_dtype="bfloat16",
                                 remat_policy=remat_policy)


def test_tpu_lowering_bf16_collective_permute(monkeypatch):
    """The pipeline's inter-stage transfers must be native bf16 on the
    TPU target — the f32 promotions are CPU-only workarounds."""
    devices = _tpu_topology_devices()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    tr = _build_abstract_trainer(devices, dp=2, tp=2, pp=2)
    batch = jax.ShapeDtypeStruct((8, 128), np.int32)
    hlo = tr.aot_lower(batch).as_text()

    cps = re.findall(r".*collective_permute.*", hlo)
    assert cps, "pipeline lowering produced no collective_permute"
    bad = [l for l in cps
           if "bf16" not in l and "f32[]" not in l and "f32<" not in l
           and "f32" in l]
    assert not bad, (
        "f32 collective_permute on the TPU target (CPU workaround "
        f"leaked into the TPU program):\n" + "\n".join(bad[:5]))
    assert any("bf16" in l for l in cps), \
        "no bf16 collective_permute found — stage boundary not bf16"
    # the attention must be the REAL Mosaic kernel on this target, not
    # the CPU interpret-mode HLO expansion
    assert "tpu_custom_call" in hlo or "custom_call" in hlo, \
        "no Mosaic custom call in the TPU program — flash kernel lost"


def test_tpu_lowering_the_stages_share_the_head(monkeypatch):
    """pp2 x dp2 x tp2, four micro-batches: the step lowered for the TPU
    deals the last stage's finished micro-batches out in ONE reduce-scatter
    of bf16 (two of the four to a stage), brings the head's ``dx`` back in
    ONE all-gather, and holds no second exchange of the buffer (PR 45;
    at the 6.7B cell's sizes: PERF.md section 6)."""
    devices = _tpu_topology_devices()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    tr = _build_abstract_trainer(devices, dp=2, tp=2, pp=2)
    hlo = tr.aot_lower(jax.ShapeDtypeStruct((8, 128), np.int32)).as_text()
    scatters = re.findall(r".*stablehlo\.reduce_scatter.*\n?.*", hlo)
    gathers = re.findall(r".*stablehlo\.all_gather.*", hlo)
    assert len(scatters) == 1 and len(gathers) == 1, (scatters, gathers)
    assert re.search(r"tensor<4x2x128x512xbf16>\) -> "
                     r"tensor<2x2x128x512xbf16>", hlo), scatters
    assert re.search(r"tensor<2x2x128x512xbf16>\) -> "
                     r"tensor<4x2x128x512xbf16>", gathers[0]), gathers


def test_tpu_topology_compile_and_memory():
    """Full compile for the v5e target: the executable exists and XLA's
    per-chip accounting is within the 16 GB v5e HBM for the tiny model
    (sanity that TPU-layout memory analysis works offline — the 13B plan
    in BENCH_13B_PLAN.json uses the same machinery)."""
    devices = _tpu_topology_devices()
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    try:
        # remat_policy="dots": full jax.checkpoint composed with the
        # layer scan trips a Mosaic "Bad lhs type" bug in the pip-bundled
        # libtpu when the flash kernel is rematerialized inside the scan
        # body (selective-dots and unroll_layers=True both avoid it; the
        # real-chip libtpu compiles all three). Selective remat is a
        # first-class production config (bench gpt uses it), so the
        # compile proof uses it.
        tr = _build_abstract_trainer(devices, dp=2, tp=2, pp=2,
                                     remat_policy="dots")
        batch = jax.ShapeDtypeStruct((8, 128), np.int32)
        compiled = tr.aot_compile(batch)
    finally:
        monkeypatch.undo()
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes)
    assert 0 < peak < 16e9, peak


def test_tpu_lowering_ring_attention_sp(monkeypatch):
    """pp×sp composition on the TPU target: the ring-attention chunk
    kernels sit inside the manual pp+sp region with tp auto — they must
    nest over the remaining axes (ring_attention._bh_kernel_shard), and
    the ring ppermutes must stay bf16."""
    devices = _tpu_topology_devices()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.distributed_strategy import \
        DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=512, num_layers=4,
                    num_heads=4, max_seq_len=512)
    with paddle.LazyGuard():
        model = GPT(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    s = DistributedStrategy()
    s.amp = True
    s.recompute = True
    s.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 2,
                        "sp_degree": 2}
    mesh = create_mesh({"dp": 1, "tp": 2, "pp": 2, "sp": 2},
                       np.array(devices)[:8])
    tr = HybridPipelineTrainer(model, opt, s, mesh, n_micro=4,
                               param_dtype="bfloat16",
                               moment_dtype="bfloat16")
    batch = jax.ShapeDtypeStruct((8, 512), np.int32)
    hlo = tr.aot_lower(batch).as_text()
    cps = re.findall(r".*collective_permute.*", hlo)
    assert any("bf16" in l for l in cps), "ring/pipeline permutes not bf16"

# ---------------------------------------------------------------------------
# The refusals only a TPU target shows (interpret mode turns a Pallas call
# into plain HLO, so a CPU mesh never sees them). Lowering is enough to
# meet XLA's "Mosaic kernels cannot be automatically partitioned" and
# Mosaic's block-shape rules; the kernels are also compiled.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dp,tp,batch", [(4, 1, 16), (2, 2, 8)])
def test_tpu_lowering_flash_without_a_pipeline_axis(monkeypatch, dp, tp,
                                                    batch):
    """pp = 1 is a fully-auto GSPMD region: every mesh axis has to be made
    manual around the flash kernel (distributed/context.kernel_scope)."""
    devices = _tpu_topology_devices()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    tr = _build_abstract_trainer(devices, dp=dp, tp=tp, pp=1)
    hlo = tr.aot_lower(jax.ShapeDtypeStruct((batch, 128), np.int32)).as_text()
    assert "tpu_custom_call" in hlo, "flash kernel lost from the program"


def test_tpu_lowering_flash_indivisible_batch_is_an_error(monkeypatch):
    """A micro-batch the dp axis does not divide has no flash spelling:
    the trainer says so, naming the shape, instead of substituting the
    jnp reference."""
    devices = _tpu_topology_devices()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    tr = _build_abstract_trainer(devices, dp=4, tp=1, pp=1)
    with pytest.raises(ValueError, match=r"batch 2 divisible by dp=4"):
        tr.aot_lower(jax.ShapeDtypeStruct((8, 128), np.int32))


def _on_tpu(device, shape, dtype):
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


#: (pages, heads, pages a slot, rows) of a toy pool, whose 4 heads do not
#: fill a tile (the kernel's batched product), and of the cells' own row
#: shapes (16 heads: a strided load a head): gpt3-1.3b-serve's 12 decode
#: rows and its one chunk row over 128 pages a slot, ouro-2.6b-serve's 10
#: rows over 32 pages
_TOY, _GPT3_ROWS, _GPT3_CHUNK, _OURO_ROWS = (
    (33, 4, 8, 4), (1537, 16, 128, 12), (1537, 16, 128, 1),
    (321, 16, 32, 10))


@pytest.mark.parametrize("shape,int8,t,layers", [
    (_TOY, int8, t, layers) for layers in (None, 3)
    for int8 in (False, True) for t in (1, 32)] + [
    (_GPT3_ROWS, False, 1, 24), (_GPT3_CHUNK, False, 32, 24),
    (_GPT3_ROWS, True, 1, 24), (_GPT3_ROWS, False, 5, 24),
    (_OURO_ROWS, False, 1, 192)])
def test_tpu_compile_ragged_pallas(monkeypatch, shape, int8, t, layers):
    """The ragged paged kernel at head_dim 128 compiles with Mosaic for
    decode rows and chunk rows, bf16 pools and int8 pools with scales
    (the [P, NH] scale rows once broke the (8, 128) block rule), over one
    layer's pools and over the stack with the layer a traced scalar that
    the kernel's own page copies read from scalar prefetch (ISSUE 32), at
    a toy pool's shapes and at the serving cells' (ISSUE 34: decode,
    verify and chunk rows of gpt3-1.3b-serve, the 192 cache layers of
    ouro-2.6b-serve)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import ragged_paged_attention

    dev = _tpu_topology_devices()[0]
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    (pages, nh, nps, r), ps, hd = shape, 16, 128
    stack = () if layers is None else (layers,)
    pool = _on_tpu(dev, stack + (pages, ps, nh, hd),
                   jnp.int8 if int8 else jnp.bfloat16)
    scale = _on_tpu(dev, stack + (pages, nh), jnp.float32) if int8 else None
    args = [_on_tpu(dev, (r, t, nh, hd), jnp.bfloat16), pool, pool,
            _on_tpu(dev, (r, nps), jnp.int32),
            _on_tpu(dev, (r,), jnp.int32), _on_tpu(dev, (r,), jnp.int32),
            scale, scale,
            None if layers is None else _on_tpu(dev, (), jnp.int32)]

    def f(q, k, v, pt, p0, tl, ks, vs, layer):
        return ragged_paged_attention(q, k, v, pt, p0, tl, impl="pallas",
                                      k_scale=ks, v_scale=vs, layer=layer)

    compiled = jax.jit(f).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if layers is not None:
        # the stack goes to the kernel whole: no layer of it is sliced out
        assert not re.search(r"= \w+\[%d,%d,%d,%d\]"
                             % (pages, ps, nh, hd), text)


def _compiled_tick(monkeypatch, model_cfg, serving_cfg):
    """(engine, compiled tick): an engine built and ticked once on the CPU,
    its tick re-lowered from the avals captured at that dispatch
    (engine._first_call) with TPU shardings, as a program traced for the
    TPU: the tick the chip runs, the attention kernel inside."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    dev = _tpu_topology_devices()[0]
    paddle.seed(0)
    net = GPT(model_cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, serving_cfg)
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.step()
    eng.drain(0)
    fn, avals = eng._program_args[eng.compiled_sites[0]]
    avals = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), avals)
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    calls = metrics.registry().counter("serving/attn_calls{path=pallas}")
    before = calls.value
    # the CPU's trace of these avals took the XLA spelling: trace anew
    jax.clear_caches()
    compiled = fn.lower(*avals).compile()
    assert calls.value > before and "tpu_custom_call" in compiled.as_text(), \
        "the tick compiled for the TPU does not hold the attention kernel"
    return eng, compiled


def _updated_in_place(eng, compiled):
    ma = compiled.memory_analysis()
    one_pool = eng.pool.k.nbytes
    assert eng.pool.k.dtype == jax.numpy.bfloat16 and one_pool > 30e6
    assert ma.alias_size_in_bytes >= 2 * one_pool, \
        "the donated page pools are not aliased"
    assert ma.temp_size_in_bytes < one_pool, \
        f"{ma.temp_size_in_bytes} bytes of temporaries: a pool is copied"


#: a toy model under pools that dwarf it (2 heads: the kernel's batched
#: product), and gpt3-1.3b-serve's rows: 12 slots of 128 pages, 16 heads of
#: 128 (a strided load a head), decode rows and a chunk row of 32
_TICKS = {
    "toy": (dict(hidden_size=256, num_heads=2, max_seq_len=128),
            dict(num_slots=2, page_size=16, num_pages=4097)),
    "gpt3-rows": (dict(hidden_size=2048, num_heads=16, max_seq_len=2048,
                       ffn_hidden_size=256),
                  dict(num_slots=12, page_size=16)),
}


@pytest.mark.parametrize("sizes", list(_TICKS))
def test_tpu_compile_serving_tick(monkeypatch, sizes):
    """The unified serving tick compiles for the v5e with the ragged
    kernel inside (ISSUE 34), and updates its bf16 pools in place there
    (ISSUE 32; the CPU's XLA widens a bf16 pool around a scatter, so only
    this target speaks for bf16): no pool-sized temporary, both pools
    aliased."""
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.serving import ServingConfig

    model, serving = _TICKS[sizes]
    eng, compiled = _compiled_tick(
        monkeypatch, GPTConfig(vocab_size=256, num_layers=2, **model),
        ServingConfig(**serving))
    _updated_in_place(eng, compiled)


#: the same two, for a looped model: ouro-2.6b-serve's rows are 10 slots
#: of 32 pages
_LOOPED_TICKS = {
    "toy": _TICKS["toy"][:1] + (dict(num_slots=2, page_size=16,
                                     num_pages=1025),),
    "ouro-rows": (dict(hidden_size=2048, num_heads=16, max_seq_len=512),
                  dict(num_slots=10, page_size=16)),
}


@pytest.mark.parametrize("sizes", list(_LOOPED_TICKS))
def test_tpu_compile_looped_serving_tick(monkeypatch, sizes):
    """ISSUE 33: a looped model's tick (4 loop steps over 2 layers: pools 8
    cache layers deep, the carry of the scan over steps and of the layer
    scan inside it; RoPE, sandwich RMSNorm, SwiGLU) compiles for the v5e
    with the ragged kernel inside and updates its bf16 pools in place
    there: no pool-sized temporary, both pools aliased."""
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.serving import ServingConfig

    model, serving = _LOOPED_TICKS[sizes]
    eng, compiled = _compiled_tick(monkeypatch, GPTConfig(
        vocab_size=256, num_layers=2, ffn_hidden_size=384,
        layer_norm_eps=1e-6, tie_word_embeddings=False, norm="rmsnorm",
        position="rope", rope_theta=1e6, bias=False, ffn="swiglu",
        sandwich_norm=True, loop_steps=4, **model), ServingConfig(**serving))
    assert eng.pool.k.shape[0] == 8
    _updated_in_place(eng, compiled)


def test_tpu_compile_a_lazy_models_state_draw(monkeypatch):
    """A ``LazyGuard`` model's served state is drawn on the v5e straight
    into the bf16 stacks: beside them the compiled draw holds less than one
    layer's float32 (models/gpt._decode_state_drawer; at Ouro-2.6B's sizes
    it compiles to 0 B of temporaries for 5.34 GB of state, PERF.md)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.models import gpt as gpt_mod
    from paddle_tpu.static.functional import state_tensors

    dev = _tpu_topology_devices()[0]
    cfg = GPTConfig(
        vocab_size=512, hidden_size=512, num_layers=6, num_heads=4,
        max_seq_len=128, ffn_hidden_size=1408, layer_norm_eps=1e-6,
        tie_word_embeddings=False, norm="rmsnorm", position="rope",
        rope_theta=1e6, bias=False, ffn="swiglu", sandwich_norm=True,
        loop_steps=4)
    with paddle.LazyGuard():
        net = GPT(cfg)
    net.bfloat16()
    blocks = list(net.blocks)
    sfx, t0 = state_tensors(blocks[0])[:2]
    in_blocks = {id(p) for b in blocks for p in b.parameters()}
    rest = [(n, p) for n, p in net.named_parameters()
            if id(p) not in in_blocks]
    drawer = gpt_mod._decode_state_drawer(sfx, t0, len(blocks), rest)
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    ma = jax.jit(drawer).lower(
        _on_tpu(dev, (2,), jnp.uint32)).compile().memory_analysis()
    assert ma.output_size_in_bytes >= 2 * cfg.num_params()
    layer_f32 = 4 * sum(int(np.prod(p._value.shape)) for p in t0)
    assert ma.temp_size_in_bytes < layer_f32, \
        f"{ma.temp_size_in_bytes} bytes of temporaries beside the stacks"


@pytest.mark.parametrize("mesh_devices", [1, 2])
def test_tpu_compile_dropless_moe_at_olmoe_widths(monkeypatch, mesh_devices):
    """One drop-less expert layer at OLMoE's widths (4,096 tokens, 64
    experts of 1,024, 8 a token), forward and gradients, compiles for the
    v5e at the operations needed and no more (a dense fallback over the
    groups would count 64 times as many). On one device all three products
    and their six backward products are the Pallas calls ``moe_gmm`` and
    ``moe_tgmm`` and XLA's ragged-dot kernel is gone; under a two-device
    auto mesh, the experts over ``ep``, they are XLA:TPU's own ragged-dot
    kernel, which GSPMD partitions (it refuses a Mosaic call there)."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import context as dctx
    from paddle_tpu.distributed.moe import dropless_moe

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    t, h, f, e, k = 4096, 2048, 1024, 64, 8
    shapes = ((t, h), (h, e), (e, h, f), (e, h, f), (e, f, h))
    mesh = Mesh(np.array(_tpu_topology_devices()[:mesh_devices]), ("ep",))
    specs = (P(), P()) + (P("ep"),) * 3
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                 sharding=NamedSharding(mesh, spec))
            for s, spec in zip(shapes, specs)]

    def loss(*a):
        y, balance, z, _ = dropless_moe(*a, top_k=k)
        return jnp.sum(y.astype(jnp.float32)) + balance + z

    # conftest asks for "highest" everywhere; with bf16 operands both
    # kernels are then refused by Mosaic ("Bad lhs type"), and bf16 products
    # are exact in the float32 accumulator at the default anyway
    with jax.default_matmul_precision("default"), dctx.kernel_scope(mesh):
        compiled = jax.jit(jax.grad(loss, range(5))).lower(*args).compile()
    text = compiled.as_text()
    calls = re.findall(
        r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* custom-call\([^\n]*"
        r"\"tpu_custom_call\"", text)
    ragged = set(re.findall(r"%(ragged-dot-none[\w.\-]*) = ", text))
    if mesh_devices == 1:
        assert not ragged
        ours = sorted((name.split(".")[0], shape) for name, shape in calls
                      if name.startswith("moe_"))
        rows = f"bf16[{t * k},"
        assert ours == sorted(
            [("moe_gmm", f"{rows}{f}]")] * 3            # gate, up, d mid
            + [("moe_gmm", f"{rows}{h}]")] * 3          # down, 2 x d xs
            + [("moe_tgmm", f"bf16[{e},{h},{f}]")] * 2
            + [("moe_tgmm", f"bf16[{e},{f},{h}]")]), calls
    else:
        assert len(ragged) >= 9 and not any(
            name.startswith("moe_") for name, _ in calls)
    # a Pallas call reports its cost estimate: 2 m k n each
    needed = 3 * 3 * 2.0 * t * k * h * f
    assert needed <= compiled.cost_analysis()["flops"] < 1.1 * needed


@pytest.mark.parametrize("cell,m,h,f,e,grad", [
    ("serve-ling3-longgen-backlog", 1024, 2560, 768, 128, False),
    ("serve-dots3-longdoc-backlog", 512, 5120, 1536, 32, False),
    ("train-solar-open2-1chip", 2560, 4096, 1280, 8, True),
])
def test_tpu_compile_the_grouped_products_at_the_cells_tiles(monkeypatch,
                                                             cell, m, h, f,
                                                             e, grad):
    """PR 50: ``tile_for`` gives a grid step the whole contraction and any
    multiple of 128 columns that fits its budget; Mosaic takes those blocks
    at the cells' widths (Ling's whole ``[2560, 768]`` expert a step, dots3's
    ``[5120, 384]``, Solar's ``[4096, 256]`` and, towards the weights,
    ``moe_tgmm``'s ``[4096, 256]`` and ``[1280, 1024]`` blocks with their
    float32 sums) inside the kernels' VMEM limit."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops import grouped_matmul as gm

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    one = SingleDeviceSharding(_tpu_topology_devices()[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def product(a, b, sizes):
        return jnp.sum(gm.pallas_grouped_matmul(a, b, sizes)
                       .astype(jnp.float32))

    for k, n in ((h, f), (f, h)):
        assert gm.tile_for(m, k, n)[1] == k
        fn = jax.grad(product, (0, 1)) if grad else product
        with jax.default_matmul_precision("default"):
            text = jax.jit(fn).lower(
                sds((m, k)), sds((e, k, n)),
                sds((e,), jnp.int32)).compile().as_text()
        # differentiated, the sum's forward product is dead: d rows and
        # d weights are left, named ``transpose_jvp_moe_...``
        assert len(re.findall(r"%[\w.\-]*moe_gmm[\w.\-]* = ", text)) == 1
        assert len(re.findall(r"%[\w.\-]*moe_tgmm[\w.\-]* = ",
                              text)) == int(grad)


def test_tpu_compile_olmoe_step_of_the_cell(monkeypatch):
    """The step of ``train-olmoe-1chip-4k`` (OLMoE at published widths,
    depth 2, 8 micro-batches of one sequence of 4,096, bf16 parameters and
    moments, full recomputation) compiles for one v5e inside its 15.75 GB,
    with the experts' products as the Pallas calls: three forward, three
    recomputed and six backward in the micro-batch loops' bodies, which
    sit inside the layer scans (PR 29: 14.38 GB in all where micro-batches
    outside needed 14.96, and no sum over the whole stack's gradient)."""
    import dataclasses

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.distributed_strategy import \
        DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.models import GPT, GPTConfig

    devices = _tpu_topology_devices()
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    cfg = dataclasses.replace(GPTConfig.olmoe_1b_7b(), num_layers=2)
    with paddle.LazyGuard():
        model = GPT(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    s = DistributedStrategy()
    s.amp = True
    s.recompute = True
    mesh = create_mesh({"dp": 1, "tp": 1, "pp": 1, "sp": 1},
                       np.array(devices)[:1])
    tr = HybridPipelineTrainer(model, opt, s, mesh, n_micro=8,
                               param_dtype="bfloat16",
                               moment_dtype="bfloat16")
    with jax.default_matmul_precision("default"):
        compiled = tr.aot_lower(
            jax.ShapeDtypeStruct((8, 4096), np.int32)).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"compiled step: {total / 1e9:.2f} GB in all "
          f"({ma.argument_size_in_bytes / 1e9:.2f} arguments, "
          f"{ma.temp_size_in_bytes / 1e9:.2f} temporaries)")
    assert total < 14.6e9 < 15.75e9, ma
    text = compiled.as_text()
    # the running sum over the micro-batches is one layer's gradient
    assert re.findall(r"%select_add_fusion[\w.\-]* = bf16\[64,", text)
    assert not re.findall(r"%select_add_fusion[\w.\-]* = bf16\[2,64,", text)
    assert len(re.findall(r"%moe_gmm[\w.\-]* = ", text)) == 9
    assert len(re.findall(r"%moe_tgmm[\w.\-]* = ", text)) == 3
    assert not re.findall(r"%ragged-dot-none[\w.\-]* = ", text)


def test_tpu_compile_a_kda_layers_gradient_at_the_cells_widths(monkeypatch):
    """ISSUE 41: ``kda_mix`` under ``jax.checkpoint`` and its gradient at
    Solar-Open2's published widths and the training cell's 8,192 tokens
    compile for one v5e with the chain between the projections and the
    scan as the Pallas pair ``kda_prep``/``kda_prep_bwd``
    (``ops/kda_prep.py``): Mosaic takes the blocks (a head's 128 columns,
    the 16-row halo view, the unaligned tap loads), both calls sit under
    ``blk/kda/proj``, where the benchmark's ``kda.proj_ms_per_step`` reads
    them, neither's name starts like the scan's kernels', and the
    program's temporaries are 2.7 GB where the ``jax.numpy`` chain's
    float32 ``[8192, 8192]`` arrays made them 4.3. ISSUE 42: the scan is
    two kernels here, the forward rule's sweep, which leaves every chunk's
    entry state and inverse (``kda_fwd_states``; the pass before it is the
    same call on the same operands and XLA keeps one), and
    ``kda_bwd_grads``: no third sweep rebuilds the states (the inverses
    are 0.13 of the 2.7 GB; the states were there before)."""
    import jax.numpy as jnp

    from paddle_tpu.models import solar_open2 as prog

    dev = _tpu_topology_devices()[0]
    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    c = prog.SolarOpen2Config()
    heads, d = c.linear_attn_num_heads, c.linear_attn_head_dim
    bf = lambda *shape: _on_tpu(dev, shape, jnp.bfloat16)
    w = {n: bf(*p.shape) for n, p in prog.SolarKDA(
        prog.SolarOpen2Config.tiny()).named_parameters()}
    sizes = {"w_q": (c.hidden_size, heads * d), "conv_q": (4, heads * d),
             "w_f1": (c.hidden_size, c.kda_proj_rank),
             "w_f2": (c.kda_proj_rank, heads * d), "dt_bias": (heads * d,),
             "A_log": (heads,), "w_b": (c.hidden_size, heads),
             "o_norm": (d,), "w_o": (heads * d, c.hidden_size)}
    like = {"w_k": "w_q", "w_v": "w_q", "conv_k": "conv_q",
            "conv_v": "conv_q", "w_g1": "w_f1", "w_g2": "w_f2",
            "b_g": "dt_bias"}
    w = {n: bf(*sizes[like.get(n, n)]) for n in w}

    def loss(x, w):
        mix = jax.checkpoint(lambda x, w: prog.kda_mix(x, w, c))
        return jnp.sum(mix(x, w).astype(jnp.float32))

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            bf(1, 8192, c.hidden_size), w).compile()
    calls = re.findall(r"%(kda_\w+?)[.\d]* = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"",
                       compiled.as_text())
    names = sorted(name for name, _ in calls)
    assert names == ["kda_bwd_grads", "kda_fwd_states", "kda_prep",
                     "kda_prep_bwd"], names
    for name, scope in calls:
        part = "blk/kda/proj" if name.startswith("kda_prep") \
            else "blk/kda/scan"
        assert part in scope, (name, scope)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9


def test_tpu_compile_the_latent_models_forward(monkeypatch):
    """ISSUE 37: the tick's forward of a latent-attention model
    (``models/dots3.dots3_ragged_apply``: latent, indexer-key and windowed
    pools, the threshold selection, the walks over a row's live pages, the
    held experts' grouped matmuls) compiles for the v5e from a ``LazyGuard``
    model, with the Pallas grouped matmul inside and the donated pools
    aliased. Published head counts and latent widths over a small hidden
    size and two experts of the held share."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.dots3 import (FULL, SLIDING, Dots3, Dots3Config,
                                         dots3_ragged_apply)
    from paddle_tpu.models.tick import state_drawer
    from paddle_tpu.serving.paged_cache import LatentPools

    dev = _tpu_topology_devices()[0]
    cfg = Dots3Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=3,
        layer_types=(FULL, FULL, SLIDING), n_routed_experts=16,
        experts_held=(0, 2), q_lora_rank=128, swa_q_lora_rank=128)
    with paddle.LazyGuard():
        net = Dots3(cfg)
    net.bfloat16()
    state = jax.eval_shape(state_drawer(net),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    ns, ps, nps, w = 4, 128, 24, 256
    spec = net.cache_spec()
    pools = jax.eval_shape(lambda: LatentPools.zeros(
        spec["full_layers"], ns * nps + 1, spec["window_layers"],
        ns * 8 + 1, ps, spec["latent_width"], spec["index_width"],
        spec["window_width"], jnp.bfloat16))
    nt = ns + w

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (state[0], state[1], pools, i32(nt), i32(nt), i32(nt),
            (i32(ns + 1, nps), i32(ns + 1, nps)), i32(ns + 1), i32(ns + 1),
            i32(ns))
    args = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), args)

    def forward(*a):
        return dots3_ragged_apply(cfg, *a, decode_rows=ns, chunk_width=w)

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(forward, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert re.findall(r"%moe_gmm[\w.\-]* = ", text)
    # ISSUE 39: the full layers' attention is the Pallas kernel, twice a
    # layer (decode rows, chunk rows), and no score block with a (heads x
    # keys) extent is an array of the program
    assert len(re.findall(r"%selected_latent_attn[\w.\-]* = ", text)) == 4
    assert not re.findall(r"f32\[\d+,\d+,128,\d{3,}\]", text)
    ma = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pools)
    assert ma.alias_size_in_bytes >= pool_bytes, \
        "the donated latent pools are not aliased"


def test_tpu_compile_the_dense_latent_models_forward(monkeypatch):
    """ISSUE 40: DeepSeek-V2's tick forward
    (``models/deepseek_v2.deepseek_v2_ragged_apply``: latent pools alone,
    dense latent attention, the group-limited router, the held experts'
    grouped matmuls, two chunk rows) compiles for the v5e from a
    ``LazyGuard`` model, its attention the Pallas kernel ``latent_attn``
    twice a layer (decode rows, chunk rows) with no selection operand, no
    score block of extent heads x keys an array of the program, the donated
    pool aliased. Published head counts and latent widths over a small
    hidden size and one group of experts held."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2, DeepseekV2Config,
                                               deepseek_v2_ragged_apply)
    from paddle_tpu.models.tick import state_drawer
    from paddle_tpu.serving.paged_cache import LatentPools

    dev = _tpu_topology_devices()[0]
    cfg = DeepseekV2Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=2, n_routed_experts=16,
        experts_held=(0, 2), q_lora_rank=128)
    with paddle.LazyGuard():
        net = DeepseekV2(cfg)
    net.bfloat16()
    state = jax.eval_shape(state_drawer(net),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    ns, ps, nps, w, nch = 4, 128, 24, 256, 2
    spec = net.cache_spec()
    pools = jax.eval_shape(lambda: LatentPools.zeros(
        spec["full_layers"], ns * nps + 1, 0, 2, ps, spec["latent_width"],
        0, 0, jnp.bfloat16))
    nt, rows = ns + nch * w, ns + nch

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (state[0], state[1], pools, i32(nt), i32(nt), i32(nt),
            (i32(rows, nps), i32(rows, nps)), i32(rows), i32(rows), i32(ns))
    args = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), args)

    def forward(*a):
        return deepseek_v2_ragged_apply(cfg, *a, decode_rows=ns,
                                        chunk_width=w)

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(forward, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert re.findall(r"%moe_gmm[\w.\-]* = ", text)
    assert len(re.findall(r"%latent_attn[\w.\-]* = ", text)) == 4
    assert not re.findall(r"%selected_latent_attn[\w.\-]* = ", text)
    assert not re.findall(r"f32\[\d+,\d+,128,\d{3,}\]", text)
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= int(np.prod(pools.latent.shape)) * 2, \
        "the donated latent pool is not aliased"


def _scalar_operands(text: str, kernel: str):
    """Of each of ``kernel``'s calls in a compiled program's ``text``: how
    many ``s32`` vectors lead its operands (a Mosaic call's scalar-prefetch
    operands come first), and the operand its second output is aliased
    to."""
    calls = [ln for ln in text.splitlines()
             if re.search(rf"%{kernel}[\w.\-]* = ", ln)]
    lead = [re.search(r"operand_layout_constraints=\{((?:s32\[\d+\]\{0\}, )*)",
                      ln).group(1).count("s32[") for ln in calls]
    alias = [int(re.search(r"output_to_operand_aliasing=\{\{1\}: \((\d+),",
                           ln).group(1)) for ln in calls]
    return lead, alias


def _hybrid_tick(monkeypatch, layers: int, told: bool):
    """Olmo-Hybrid's tick forward compiled for the v5e from a ``LazyGuard``
    model of ``layers`` layers at the published widths, the cell's 40 decode
    rows and its chunk row of 256 -> ``(text, memory_analysis, pools)``.
    ``told``: ``has_chunks`` an argument of the program, as the engine's is
    (ISSUE 55); else not given, the forward of one body."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.olmo_hybrid import (OlmoHybrid, OlmoHybridConfig,
                                               olmo_hybrid_ragged_apply)
    from paddle_tpu.models.tick import state_drawer
    from paddle_tpu.serving.paged_cache import StatePools

    dev = _tpu_topology_devices()[0]
    cfg = OlmoHybridConfig(num_hidden_layers=layers, vocab_size=1024)
    with paddle.LazyGuard():
        net = OlmoHybrid(cfg)
    net.bfloat16()
    state = jax.eval_shape(state_drawer(net),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    ns, ps, nps, w = 40, 16, 88, 256
    pools = jax.eval_shape(lambda: StatePools.zeros(
        net.cache_spec(), ns * nps + 1, ps, ns, jnp.bfloat16))
    assert pools.kv.k.shape == (layers // 4, ns * nps + 1, ps, 32, 128)
    assert pools.state.shape == (layers // 4 * 3, ns + 1, 15, 96, 384)
    nt = ns + w

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (state[0], state[1], pools, i32(nt), i32(nt), i32(nt),
            (i32(ns + 1, nps), i32(ns + 1)), i32(ns + 1), i32(ns + 1),
            i32(ns)) + ((jax.ShapeDtypeStruct((), jnp.bool_),) * told)
    args = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), args)

    def forward(*a):
        return olmo_hybrid_ragged_apply(
            cfg, *a[:10], decode_rows=ns, chunk_width=w,
            has_chunks=a[10] if told else None)

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(forward, donate_argnums=2).lower(*args).compile()
    return compiled.as_text(), compiled.memory_analysis(), pools


def test_tpu_compile_the_hybrid_models_forward(monkeypatch):
    """ISSUE 44: Olmo-Hybrid's tick forward
    (``models/olmo_hybrid.olmo_hybrid_ragged_apply``: K/V pages at 32 head
    rows for 30 heads, a float32 state and a convolution's history a slot)
    compiles for the v5e from a ``LazyGuard`` model at the published widths
    of one period (three linear layers and a full one), the cell's 40 decode
    rows and its chunk row of 256: the two kernels of the served delta rule
    and the ragged kernel are in the program (a chunk row attends in pieces
    of 32 queries: 64 of 32 head rows pass the kernel's VMEM), and the
    donated pools, the 1.1 GB of states among them, are aliased."""
    text, ma, pools = _hybrid_tick(monkeypatch, 4, told=False)
    assert len(re.findall(r"%gdn_step[\w.\-]* = ", text)) == 3
    assert len(re.findall(r"%gdn_chunk[\w.\-]* = ", text)) == 3
    # ISSUE 59: the rows' lengths are the rule's fourth scalar operand (a
    # row of no tokens skips it), the state stack the tenth of the call
    assert _scalar_operands(text, "gdn_chunk") == ([4] * 3, [9] * 3)
    # ISSUE 46: what lies between projections and rule is one call a row
    # group, the history read and written inside it: no gather, scatter or
    # copy of the history's stack is left in the program
    assert len(re.findall(r"%gdn_prep_step[\w.\-]* = ", text)) == 3
    assert len(re.findall(r"%gdn_prep_chunk[\w.\-]* = ", text)) == 3
    assert pools.conv.shape == (3, 3, 48, 11520)
    moved = [ln for ln in text.splitlines()
             if re.search(r" = bf16\[3,3,48,11520\]\S* (?!custom-call|"
                          r"parameter|get-tuple-element|bitcast)", ln)]
    assert not moved, moved[:3]
    assert "remat_compressed" not in text
    assert len(re.findall(r"%ragged_paged_attn[\w.\-]* = ", text)) == 2
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pools))
    assert ma.alias_size_in_bytes >= pool_bytes, \
        "the donated pools and states are not aliased"
    assert ma.temp_size_in_bytes < pools.state.size * 4 / 3, \
        "a layer's states are copied"


def test_tpu_compile_the_hybrid_models_tick_told_of_its_chunks(monkeypatch):
    """ISSUE 55: the engine's program, ``has_chunks`` an argument and the
    dense stretches under ``TickRows.dense``'s ``cond``s, at the cell's own
    sixteen layers: ISSUE 46's guard on the history's stack (40 MB for the
    twelve linear layers) holds to the letter, every kernel stands outside
    the branches once a layer, no branch carries a pool, and the donated
    pools are aliased with less than one layer's states in temporaries.
    (At one period's depth XLA takes the 10 MB stack into its fast memory
    around a ``conditional`` and writes it back, a ``copy-start`` of the
    whole stack that no cell's program has; the guard is therefore held at
    the depth the cell runs, which that memory cannot hold.)"""
    text, ma, pools = _hybrid_tick(monkeypatch, 16, told=True)
    for kernel, n in (("gdn_step", 12), ("gdn_chunk", 12),
                      ("gdn_prep_step", 12), ("gdn_prep_chunk", 12),
                      ("ragged_paged_attn", 8)):
        assert len(re.findall(rf"%{kernel}[\w.\-]* = ", text)) == n, kernel
    assert _scalar_operands(text, "gdn_chunk") == ([4] * 12, [9] * 12)
    assert pools.conv.shape == (12, 3, 48, 11520)
    moved = [ln for ln in text.splitlines()
             if re.search(r" = bf16\[12,3,48,11520\]\S* (?!custom-call|"
                          r"parameter|get-tuple-element|bitcast)", ln)]
    assert not moved, moved[:3]
    # (nor taken to fast memory and back, whole or a layer at a time)
    assert not [ln for ln in text.splitlines() if "3,48,11520]" in ln
                and re.search(r" (copy|slice)-start\(", ln)]
    assert "remat_compressed" not in text
    # a dense stretch a branch (before the first layer, between two, after
    # the last), and no pool's shape among a branch's operands or results
    branches = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert len(branches) == 17
    for a in jax.tree_util.tree_leaves(pools):
        dims = ",".join(map(str, a.shape))
        assert not [ln for ln in branches if f"[{dims}]" in ln], dims
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pools))
    assert ma.alias_size_in_bytes >= pool_bytes, \
        "the donated pools and states are not aliased"
    assert ma.temp_size_in_bytes < pools.state.size * 4 / 12, \
        "a layer's states are copied"


def test_tpu_compile_the_ling3_models_forward(monkeypatch):
    """ISSUE 49: Ling-3.0-flash's tick forward (``models/ling3.
    ling3_ragged_apply``: a float32 state of 32 heads of 128 x 128 a slot,
    each head alone on whole tiles, beside latent rows of 576 in pages of
    128; a 512-way sigmoid router limited to 4 of 8 groups with 128 experts
    held) compiles for the v5e from a ``LazyGuard`` model at the published
    widths of the dense layer, two KDA layers and the MLA layer, the cell's
    64 decode rows and its chunk row of 256: the served per-channel rule's
    two kernels, the pass before them, the dense latent kernel and the
    grouped matmuls are in the program, and the donated pools are aliased
    and no layer's states are copied."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.ling3 import (Ling3, Ling3Config,
                                         ling3_ragged_apply)
    from paddle_tpu.models.tick import state_drawer
    from paddle_tpu.serving.paged_cache import LatentPools, StatePools

    dev = _tpu_topology_devices()[0]
    cfg = Ling3Config(num_hidden_layers=4, layer_ids=(1, 3, 4, 5),
                      vocab_size=1024, experts_held=(0, 128))
    assert cfg.layer_kinds == ("kda", "kda", "kda", "mla")
    with paddle.LazyGuard():
        net = Ling3(cfg)
    net.bfloat16()
    state = jax.eval_shape(state_drawer(net),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    ns, ps, nps, w = 64, 128, 133, 256
    pools = jax.eval_shape(lambda: StatePools.zeros(
        net.cache_spec(), ns * nps + 1, ps, ns, jnp.bfloat16))
    assert isinstance(pools.kv, LatentPools)
    assert pools.kv.latent.shape == (1, ns * nps + 1, 576, ps)
    assert pools.state.shape == (3, ns + 1, 32, 128, 128)
    assert pools.conv.shape == (3, 3, 80, 12288)
    nt = ns + w

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (state[0], state[1], pools, i32(nt), i32(nt), i32(nt),
            (i32(ns + 1, nps), i32(ns + 1)), i32(ns + 1), i32(ns + 1),
            i32(ns))
    args = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), args)

    def forward(*a):
        return ling3_ragged_apply(cfg, *a, decode_rows=ns, chunk_width=w)

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(forward, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%kda_step[\w.\-]* = ", text)) == 3
    assert len(re.findall(r"%kda_chunk[\w.\-]* = ", text)) == 3
    assert len(re.findall(r"%gdn_prep_step[\w.\-]* = ", text)) == 3
    assert len(re.findall(r"%gdn_prep_chunk[\w.\-]* = ", text)) == 3
    assert not re.findall(r"%gdn_step[\w.\-]* = ", text)
    assert len(re.findall(r"%latent_attn[\w.\-]* = ", text)) == 2
    assert re.findall(r"%moe_gmm[\w.\-]* = ", text)
    assert "remat_compressed" not in text
    ma = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pools))
    assert ma.alias_size_in_bytes >= pool_bytes, \
        "the donated pools and states are not aliased"
    assert ma.temp_size_in_bytes < pools.state.size * 4 / 3, \
        "a layer's states are copied"


def test_tpu_compile_the_falcon_h1_models_forward(monkeypatch):
    """ISSUE 54: Falcon-H1's tick forward (``models/falcon_h1.
    falcon_h1_ragged_apply``: in every layer a float32 SSD state of 32 heads
    of 256 x 128 a slot beside grouped K/V pages of 4 heads under 20)
    compiles for the v5e from a ``LazyGuard`` model at the published widths
    of two layers, the cell's 80 decode rows and its chunk row of 256: the
    rule's two kernels, the pass before them and the grouped ragged kernel
    are in the program (the decode rows' call and the chunk row's), the
    donated pools are aliased, no layer's states are copied and no pool is
    re-laid around a write."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.falcon_h1 import (FalconH1, FalconH1Config,
                                             falcon_h1_ragged_apply)
    from paddle_tpu.models.tick import state_drawer
    from paddle_tpu.serving.paged_cache import GroupedPools, SSDStatePools

    dev = _tpu_topology_devices()[0]
    cfg = FalconH1Config(num_hidden_layers=2, vocab_size=1024)
    with paddle.LazyGuard():
        net = FalconH1(cfg)
    net.bfloat16()
    state = jax.eval_shape(state_drawer(net),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    ns, ps, nps, w = 80, 16, 88, 256
    pools = jax.eval_shape(lambda: SSDStatePools.zeros(
        net.cache_spec(), ns * nps + 1, ps, ns, jnp.bfloat16))
    assert isinstance(pools.kv, GroupedPools)
    assert pools.kv.kv.shape == (2, ns * nps + 1, 8, ps, 128)
    assert pools.state.shape == (2, ns + 1, 32, 256, 128)
    assert pools.conv.shape == (2, 3, 96, 5120)
    nt = ns + w

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    # the engine's program: ``has_chunks`` an argument (ISSUE 55)
    args = (state[0], state[1], pools, i32(nt), i32(nt), i32(nt),
            (i32(ns + 1, nps), i32(ns + 1)), i32(ns + 1), i32(ns + 1),
            i32(ns), jax.ShapeDtypeStruct((), jnp.bool_))
    args = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), args)

    def forward(*a):
        return falcon_h1_ragged_apply(cfg, *a[:-1], decode_rows=ns,
                                      chunk_width=w, has_chunks=a[-1])

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(forward, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%ssd_step[\w.\-]* = ", text)) == 2
    assert len(re.findall(r"%ssd_chunk[\w.\-]* = ", text)) == 2
    assert len(re.findall(r"%ssd_prep_step[\w.\-]* = ", text)) == 2
    assert len(re.findall(r"%ssd_prep_chunk[\w.\-]* = ", text)) == 2
    assert len(re.findall(r"%grouped_paged_attn[\w.\-]* = ", text)) == 4
    assert not re.findall(r"%ragged_paged_attn[\w.\-]* = ", text)
    # a dense stretch a branch (before the first layer, between the two,
    # after the second), none around a kernel and none that carries a pool
    assert len(re.findall(r" conditional\(", text)) == 3
    assert "remat_compressed" not in text
    ma = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pools))
    assert ma.alias_size_in_bytes >= pool_bytes, \
        "the donated pools and states are not aliased"
    assert ma.temp_size_in_bytes < pools.state.size * 4 / 2, \
        "a layer's states are copied"


def test_tpu_compile_the_laguna_models_forward(monkeypatch):
    """ISSUE 57: Laguna's tick forward (``models/laguna.laguna_ragged_apply``:
    full attention of 48 query heads and windowed attention of 72 over one
    set of 8 key/value heads, a gate a head, held experts) compiles for the
    v5e from a ``LazyGuard`` model at the published widths of one full and
    one windowed layer, both sparse (8 of the 256 experts held), the cell's
    38 decode rows and its chunk row of 256: the grouped kernel and its
    windowed sibling are in the program (the decode rows' call and the chunk
    row's each), the donated pools are aliased and none is re-laid around a
    write."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.laguna import (FULL, SLIDING, Laguna,
                                          LagunaConfig, laguna_ragged_apply)
    from paddle_tpu.models.tick import state_drawer
    from paddle_tpu.serving.paged_cache import WindowedKVPools

    dev = _tpu_topology_devices()[0]
    cfg = LagunaConfig(num_hidden_layers=2, vocab_size=1024,
                       layer_types=(FULL, SLIDING),
                       mlp_layer_types=("sparse", "sparse"),
                       num_attention_heads_per_layer=(48, 72),
                       experts_held=(0, 8))
    with paddle.LazyGuard():
        net = Laguna(cfg)
    net.bfloat16()
    state = jax.eval_shape(state_drawer(net),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    ns, ps, nps, w = 38, 16, 1104, 256
    held = -(-(511 + w) // ps) + 2
    pools = jax.eval_shape(lambda: WindowedKVPools.zeros(
        1, ns * nps + 1, 1, ns * held + 1, ps, 8, 128, jnp.bfloat16))
    assert pools.kv.kv.shape == (1, ns * nps + 1, 16, ps, 128)
    assert pools.window.kv.shape == (1, ns * 50 + 1, 16, ps, 128)
    nt = ns + w

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    args = (state[0], state[1], pools, i32(nt), i32(nt), i32(nt),
            (i32(ns + 1, nps), i32(ns + 1, nps)), i32(ns + 1), i32(ns + 1),
            i32(ns), jax.ShapeDtypeStruct((), jnp.bool_))
    args = jax.tree_util.tree_map(
        lambda a: _on_tpu(dev, a.shape, a.dtype), args)

    def forward(*a):
        return laguna_ragged_apply(cfg, *a[:-1], decode_rows=ns,
                                   chunk_width=w, has_chunks=a[-1])

    monkeypatch.setenv("PADDLE_TPU_TARGET_PLATFORM", "tpu")
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(forward, donate_argnums=2).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%grouped_paged_attn[\w.\-]* = ", text)) == 2
    assert len(re.findall(r"%grouped_window_attn[\w.\-]* = ", text)) == 2
    assert len(re.findall(r" conditional\(", text)) == 3
    assert "remat_compressed" not in text
    ma = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(pools))
    assert ma.alias_size_in_bytes >= pool_bytes, \
        "the donated pools are not aliased"
    assert ma.temp_size_in_bytes < pool_bytes / 4, "a pool is copied"
