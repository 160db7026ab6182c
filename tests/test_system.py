"""System-level behaviour: sharding rules, HLO analysis, serve loop,
MoE routing invariants, end-to-end OFU pipeline sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.configs import SHAPES, get_config, input_specs
from repro.launch.hlo_analysis import analyze, multiplicities, parse_module

# ---------------------------------------------------------------------------
# sharding rules (AbstractMesh: no devices needed)
# ---------------------------------------------------------------------------
try:
    AM = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
except TypeError:
    try:  # jax ~0.4.3x: a single tuple of (name, size) pairs
        AM = jax.sharding.AbstractMesh((("data", 16), ("model", 16)))
    except TypeError:  # older keyword signature
        AM = jax.sharding.AbstractMesh(axis_sizes=(16, 16),
                                       axis_names=("data", "model"))


def _spec(path, shape):
    from repro.launch.sharding import param_spec
    return param_spec(path, shape, AM, ("data",), "model")


def test_param_specs_core_rules():
    P = jax.sharding.PartitionSpec
    # column-parallel: tp on last dim, fsdp on the contracting dim
    assert _spec("['layers']['attn']['wq']", (32, 2048, 4096)) \
        == P(None, "data", "model")
    # row-parallel: tp on contracting dim
    assert _spec("['layers']['attn']['wo']", (32, 4096, 2048)) \
        == P(None, "model", "data")
    # expert-parallel: tp on the expert dim
    assert _spec("['moe_layers']['mlp']['experts']['wi']",
                 (58, 256, 7168, 2048)) == P(None, "model", "data", None)
    # vocab-parallel embed
    assert _spec("['embed']", (128256, 4096)) == P("model", "data")
    # divisibility guard: a 50-wide dim must stay unsharded
    assert _spec("['layers']['attn']['wq']", (12, 50, 50)) == P(None, None,
                                                                None)
    # optimizer moments inherit the parameter rule
    assert _spec("['mu']['layers']['attn']['wq']['m']", (32, 2048, 4096)) \
        == P(None, "data", "model")
    # factored moment rows (dim dropped) stay in range
    assert _spec("['mu']['layers']['attn']['wq']['v']['row']", (32, 2048)) \
        is not None


def test_batch_shardings_cover_all_inputs():
    from repro.launch.sharding import batch_shardings
    for arch in ("qwen3-4b", "deepseek-v3-671b", "mamba2-780m", "zamba2-7b",
                 "whisper-small", "phi-3-vision-4.2b"):
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if not cfg.supports_shape(shape):
                continue
            sh = batch_shardings(cfg, shape, AM, ("data",), "model")
            specs = input_specs(cfg, shape)
            assert set(sh) == set(specs), (arch, sname)
            # every sharded dim must divide the axis
            for k, ns in sh.items():
                dims = specs[k].shape
                for i, ax in enumerate(ns.spec):
                    if ax is None or i >= len(dims):
                        continue
                    size = AM.shape[ax] if isinstance(ax, str) else \
                        int(np.prod([AM.shape[a] for a in ax]))
                    assert dims[i] % size == 0, (arch, sname, k, i)


# ---------------------------------------------------------------------------
# serving-mode sharding (§Perf cell B: EP² + no-FSDP decode layout)
# ---------------------------------------------------------------------------
def test_serving_param_specs_ep2():
    from repro.launch.sharding import param_spec
    P = jax.sharding.PartitionSpec
    # v3 experts (58, 256, 7168, 2048): EP over the FULL mesh when serving
    s = param_spec("['moe_layers']['mlp']['experts']['wi']",
                   (58, 256, 7168, 2048), AM, ("data",), "model",
                   fsdp=False, serving=True)
    assert s == P(None, ("data", "model"), None, None)
    # 64 experts don't divide 256 -> divisibility guard falls back to tp
    s = param_spec("['moe_layers']['mlp']['experts']['wi']",
                   (27, 64, 2048, 1408), AM, ("data",), "model",
                   fsdp=False, serving=True)
    assert s == P(None, "model", None, None)
    # non-expert weights: TP only, replicated over data (no FSDP gathers)
    s = param_spec("['dense_layers']['attn']['wq']", (61, 7168, 24576),
                   AM, ("data",), "model", fsdp=False, serving=True)
    assert s == P(None, None, "model")


def test_shardctx_ep_resolution():
    from repro.models.common import ShardCtx
    ctx = ShardCtx(mesh=AM, dp=("data",), tp="model",
                   ep=("data", "model"))
    assert ctx.ep_covers_dp
    assert ctx.spec("ep").spec == jax.sharding.PartitionSpec(
        ("data", "model"))
    ctx2 = ShardCtx(mesh=AM, dp=("data",), tp="model")
    assert not ctx2.ep_covers_dp
    assert ctx2.ep_axes == "model"


# ---------------------------------------------------------------------------
# HLO analysis
# ---------------------------------------------------------------------------
_FAKE_HLO = """\
HloModule test

%loop_cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %iv = s32[] get-tuple-element(%p), index=0
  %limit = s32[] constant(10)
  ROOT %cmp = pred[] compare(%iv, %limit), direction=LT
}

%loop_body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %x = f32[8,8] get-tuple-element(%p), index=1
  %w = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8] all-reduce(%d), replica_groups={{0,1,2,3}}, to_apply=%sum
  %iv = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8,8]) tuple(%iv, %ar)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (in: f32[8,8]) -> (s32[], f32[8,8]) {
  %in = f32[8,8] parameter(0)
  %c = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%c, %in)
  ROOT %w = (s32[], f32[8,8]) while(%init), condition=%loop_cond, body=%loop_body
}
"""


def test_hlo_trip_count_and_flops():
    st_ = analyze(_FAKE_HLO, 4)
    # dot: 2*8*8*8 = 1024 flops, x10 trips
    assert st_.flops == pytest.approx(10 * 1024)
    # all-reduce: 8*8*4B * 2 * (3/4) wire bytes, x10
    assert st_.collective_bytes["all-reduce"] == pytest.approx(
        10 * 256 * 2 * 0.75)
    assert st_.collective_counts["all-reduce"] == 10


def test_hlo_multiplicities():
    mod = parse_module(_FAKE_HLO)
    mult = multiplicities(mod)
    assert mult[mod.entry] == 1.0
    assert mult["loop_body"] == 10.0
    assert mult["loop_cond"] == 11.0


# ---------------------------------------------------------------------------
# serve loop: multi-step decode consistency (integration)
# ---------------------------------------------------------------------------
def test_serve_loop_runs_all_families():
    from repro.launch.serve import init_caches
    from repro.train.steps import make_serve_step
    from repro.models import init_params
    for arch in ("granite-3-2b", "mamba2-780m", "deepseek-v3-671b"):
        cfg = get_config(arch).smoke()
        params = init_params(cfg, jax.random.key(0))
        serve = jax.jit(make_serve_step(cfg))
        B, S = 2, 16
        batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
                 "cache_index": jnp.asarray(0, jnp.int32),
                 **init_caches(cfg, B, S)}
        for i in range(4):
            nxt, caches = serve(params, batch)
            assert nxt.shape == (B, 1)
            assert (np.asarray(nxt) >= 0).all()
            assert (np.asarray(nxt) < cfg.vocab_size).all()
            batch = {"tokens": nxt.astype(jnp.int32),
                     "cache_index": jnp.asarray(i + 1, jnp.int32), **caches}


# ---------------------------------------------------------------------------
# MoE routing invariants (property-based)
# ---------------------------------------------------------------------------
@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_moe_routing_finite_and_balanced(seed):
    from repro.models.moe import moe_apply, moe_init
    cfg = get_config("deepseek-moe-16b").smoke()
    rng = np.random.default_rng(seed)
    p = moe_init(jax.random.key(seed % 1000), cfg, jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)) * 0.5,
                    jnp.float32)
    y, stats = moe_apply(cfg, p, x, None, router_stats=True)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    # sequence-wise balance term sum_i f_i P_i: 1 when balanced
    assert float(stats["balance"]) >= 0.9
    assert float(stats["load"].sum()) == 2 * 16 * cfg.top_k


def test_moe_decode_single_group_matches_batched():
    """The one-group decode routing (§Perf B2) must be numerically
    identical to routing the same tokens as a (1, B) sequence."""
    from repro.models.moe import moe_apply, moe_init
    cfg = get_config("deepseek-moe-16b").smoke()
    rng = np.random.default_rng(0)
    p = moe_init(jax.random.key(3), cfg, jnp.float32)
    xb = jnp.asarray(rng.standard_normal((8, 1, cfg.d_model)) * 0.5,
                     jnp.float32)
    y_dec = moe_apply(cfg, p, xb, None)           # (B,1,d) path
    y_seq = moe_apply(cfg, p, xb.reshape(1, 8, -1), None)
    np.testing.assert_allclose(np.asarray(y_dec).reshape(8, -1),
                               np.asarray(y_seq)[0], rtol=1e-5, atol=1e-5)


def test_ofu_end_to_end_pipeline():
    """counters -> scrape -> job OFU -> divergence: the full §V loop."""
    from repro.fleet import JobSpec, simulate_job
    from repro.fleet.divergence import JobPoint, analyze as fleet_analyze
    jobs = []
    rng = np.random.default_rng(1)
    for i in range(12):
        arch = ["qwen3-4b", "granite-3-2b", "llama3.2-3b"][i % 3]
        t = simulate_job(JobSpec(f"j{i}", arch, chips=64,
                                 true_duty=float(rng.uniform(0.2, 0.5)),
                                 duration_s=120, seed=i), max_devices=1)
        jobs.append(JobPoint(f"j{i}", arch, 64, t.app_mfu, t.ofu))
    rep = fleet_analyze(jobs)
    assert rep.r_all > 0.95  # healthy fleet: tight correlation
    assert rep.mae_all < 0.05
