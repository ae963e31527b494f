"""SPMD-aware stitching: one plan, planned per-shard, replayed on every
shard through ``shard_map``.

In-process tests cover the pieces that need no real multi-device mesh:
``_fit_spec`` repair/dedupe, ``ShardCtx`` local-shape math, plan-cache
signatures (a mesh can never collide with mesh-free), and the
collective-as-boundary planning contract (an explicit (1, 1) host mesh
exercises the whole sharded pipeline on a single device).  True
8-device numerics run in
subprocesses via the ``run_sharded`` fixture, where
``--xla_force_host_platform_device_count`` can be set before jax init.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import StitchedFunction, stitched_jit
from repro.core.plan_cache import FORMAT_VERSION, PlanCache, graph_signature
from repro.core.shard import ShardCtx, ShardSpecError, ambient_mesh_key
from repro.core.tracer import trace
from repro.dist.partitioning import _fit_spec, use_mesh
from repro.launch.mesh import make_test_mesh
from repro.runtime import RUNG_BASELINE

rng = np.random.default_rng(47)


class FakeMesh:
    """Shape-only mesh stand-in: signature/spec math without devices."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


# ---------------------------------------------------------------------------
# _fit_spec repair + dedupe
# ---------------------------------------------------------------------------
def test_fit_spec_moe_tp_rule_moves_expert_axis():
    # 40 experts on a 16-way axis cannot shard; the axis must move to
    # the last divisible unsharded dim (d_ff), not silently replicate.
    mesh = FakeMesh(model=16)
    spec = _fit_spec(P("model", None, None), (40, 1024, 4096), mesh)
    assert spec == P(None, None, "model")


def test_fit_spec_move_false_drops_instead():
    mesh = FakeMesh(model=16)
    spec = _fit_spec(P("model", None, None), (40, 1024, 4096), mesh,
                     move=False)
    assert spec == P(None, None, None)


def test_fit_spec_dedupes_repeated_axis():
    # "data" already shards dim 0 (inside the ("pod", "data") tuple);
    # a second appearance must drop, not produce an invalid sharding.
    mesh = FakeMesh(pod=2, data=4, model=2)
    spec = _fit_spec(P(("pod", "data"), None, "data"), (64, 32, 64), mesh)
    assert spec == P(("pod", "data"), None, None)


def test_fit_spec_homeless_axis_never_lands_on_used_name():
    # dim 0 (40) rejects the 16-way axis -> homeless; dim 1 keeps its
    # own copy of "model", so the homeless one must vanish rather than
    # double-shard the array.
    mesh = FakeMesh(model=16)
    spec = _fit_spec(P("model", "model", None), (40, 64, 64), mesh)
    assert spec == P(None, "model", None)


# ---------------------------------------------------------------------------
# ShardCtx
# ---------------------------------------------------------------------------
def test_shard_ctx_local_shapes_and_errors():
    ctx = ShardCtx.build(FakeMesh(data=4, model=2),
                         in_specs=(P("data", None), P(None, "model")),
                         out_specs=(P("data", None),))
    assert ctx.explicit and ctx.n_devices == 8
    assert ctx.local_shape((8, 16), P("data", None)) == (2, 16)
    assert ctx.local_shape((8, 16), P(None, "model")) == (8, 8)
    assert ctx.local_shape((8, 16), P()) == (8, 16)
    assert ctx.local_shape((8, 16), P(("data", "model"), None)) == (1, 16)
    with pytest.raises(ShardSpecError):
        ctx.local_shape((6, 16), P("data", None))  # 6 % 4 != 0
    assert ctx.mesh_key() == (("data", 4), ("model", 2))
    assert ctx.axis_env() == [("data", 4), ("model", 2)]


def test_shard_ctx_single_spec_shorthand_and_signature():
    ctx = ShardCtx.build(FakeMesh(data=4, model=2),
                         in_specs=(P("data"),), out_specs=P("data"))
    assert ctx.in_specs == (P("data"),)
    assert ctx.out_specs == (P("data"),)     # bare P wrapped, not exploded
    items = ctx.signature_items()
    other = ShardCtx.build(FakeMesh(data=8, model=2),
                           in_specs=(P("data"),), out_specs=P("data"))
    assert items != other.signature_items()  # mesh shape is hashed


def test_input_specs_from_names_resolve_and_repair():
    from repro.core.shard import input_specs_from_names

    mesh = FakeMesh(data=4, model=2)
    specs = input_specs_from_names(mesh, [
        ("act_btd", (8, 128, 512)),
        ("act_bhsd", (8, 16, 128, 64)),
        ("", (512, 512)),                 # unnamed: replicated
        ("act_btd", (6, 128, 512)),       # 6 % 4 != 0: dropped, not moved
    ])
    assert specs == (P(("data",), None, None),
                     P(("data",), "model", None, None),
                     P(),
                     P(None, None, None))


def test_ambient_mesh_key_tracks_use_mesh():
    assert ambient_mesh_key() is None
    with use_mesh(FakeMesh(data=4, model=2)):
        assert ambient_mesh_key() == (("data", 4), ("model", 2))
    with use_mesh(FakeMesh(data=1, model=1)):
        assert ambient_mesh_key() is None    # 1 device: mesh-free keys
    assert ambient_mesh_key() is None


# ---------------------------------------------------------------------------
# plan-cache v7 signatures
# ---------------------------------------------------------------------------
def _chain(x):
    y = jnp.tanh(x) * 0.5 + 1.0
    return jnp.exp(-y) + y


def test_mesh_keys_signature_no_1dev_8dev_collision():
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    graph = trace(_chain, x)
    from repro.core.cost_model import V5E

    base = graph_signature(graph, V5E)
    ambient8 = ShardCtx(mesh=FakeMesh(data=4, model=2))
    ambient2 = ShardCtx(mesh=FakeMesh(data=1, model=2))
    s8 = graph_signature(graph, V5E, shard=ambient8)
    s2 = graph_signature(graph, V5E, shard=ambient2)
    assert len({base, s8, s2}) == 3
    # shard=None hashes nothing: mesh-free signatures are bit-stable
    assert base == graph_signature(graph, V5E, shard=None)


def test_sharded_and_meshfree_entries_roundtrip_independently(tmp_path):
    x = np.asarray(rng.integers(-2, 3, (8, 16)), np.float32)
    mesh = make_test_mesh(1)
    kw = dict(mesh=mesh, in_specs=(P(),), out_specs=(P(),))

    rep_free = StitchedFunction(_chain, plan_cache=str(tmp_path)).report(x)
    rep_shard = StitchedFunction(_chain, plan_cache=str(tmp_path),
                                 **kw).report(x)
    assert rep_free.signature != rep_shard.signature
    pc = PlanCache(str(tmp_path))
    e_free = pc.load(rep_free.signature)
    e_shard = pc.load(rep_shard.signature)
    assert e_free is not None and e_free["format"] == FORMAT_VERSION
    assert "mesh" not in e_free
    assert e_shard is not None and e_shard["format"] == FORMAT_VERSION
    assert e_shard["mesh"] == {"shape": [1, 1], "axes": ["data", "model"]}

    # a second process replays each entry from its own signature
    rep2 = StitchedFunction(_chain, plan_cache=str(tmp_path)).report(x)
    rep3 = StitchedFunction(_chain, plan_cache=str(tmp_path), **kw).report(x)
    assert rep2.plan_cache_hit and rep2.signature == rep_free.signature
    assert rep3.plan_cache_hit and rep3.signature == rep_shard.signature


# ---------------------------------------------------------------------------
# collectives bound groups; flanking chains still stitch
# ---------------------------------------------------------------------------
def _psum_sandwich(x):
    h = x * 2.0 + 1.0
    h = jnp.tanh(h) * x
    h = h - jnp.maximum(h, 0.0) * 0.1
    s = jax.lax.psum(h, "model")
    y = s * 0.5 + 3.0
    y = jnp.exp(-y) + y
    return y * y + 1.0


def test_collective_is_hard_group_boundary():
    sf = stitched_jit(_psum_sandwich, mesh=make_test_mesh(1),
                      in_specs=(P(),), out_specs=(P(),))
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    out = sf(x)
    rep = sf.report(x)
    assert rep.sharded and rep.n_collective == 1
    # the psum split the chain: >= 2 groups, >= 1 split caused by the
    # collective itself, and the flanking elementwise chains still
    # folded into their neighboring groups (not left as bare ops).
    assert rep.n_groups >= 2
    assert rep.collective_boundaries >= 1
    assert not rep.fallbacks and rep.rung != RUNG_BASELINE
    h = x * 2.0 + 1.0
    h = jnp.tanh(h) * x
    h = h - jnp.maximum(h, 0.0) * 0.1       # psum over size-1 axis: identity
    y = h * 0.5 + 3.0
    y = jnp.exp(-y) + y
    np.testing.assert_allclose(np.asarray(out), np.asarray(y * y + 1.0),
                               rtol=1e-5, atol=1e-5)


def test_explicit_shard_api_validation():
    with pytest.raises(ValueError):
        StitchedFunction(_chain, in_specs=(P(),))          # specs, no mesh
    with pytest.raises(ValueError):
        StitchedFunction(_chain, mesh=make_test_mesh(1),
                         in_specs=(P(),))                  # missing out_specs
    with pytest.raises(ValueError):
        stitched_jit(_chain, differentiable=True, mesh=make_test_mesh(1),
                     in_specs=(P(),), out_specs=(P(),))
    with pytest.raises(ValueError):
        StitchedFunction(_chain, dispatch="interpret",
                         mesh=make_test_mesh(1), in_specs=(P(),),
                         out_specs=(P(),))


# ---------------------------------------------------------------------------
# 8-device numerics (subprocess: forced host devices)
# ---------------------------------------------------------------------------
_CHILD_COMMON = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core import stitched_jit
from repro.launch.mesh import make_test_mesh

assert len(jax.devices()) == 8, jax.devices()
mesh = make_test_mesh(8)          # (data=4, model=2)
rng = np.random.default_rng(3)

def block(x, w1, w2):
    # Megatron-style per-shard MLP block: column-parallel w1,
    # row-parallel w2, psum combine, elementwise epilogue + residual.
    h = jnp.maximum(x @ w1, 0.0) * 0.5
    y = h @ w2
    y = jax.lax.psum(y, "model")
    y = jnp.tanh(y * 0.0625) + x
    return y * 2.0

def block_ref(x, w1, w2):
    h = jnp.maximum(x @ w1, 0.0) * 0.5
    y = h @ w2
    y = jnp.tanh(y * 0.0625) + x
    return y * 2.0

BLOCK_SPECS = dict(in_specs=(P("data", None), P(None, "model"),
                             P("model", None)),
                   out_specs=P("data", None))

def moe(x, w, g):
    # expert-parallel mixture: local experts partial-sum, psum combine.
    h = jnp.einsum("td,edf->etf", x, w)
    h = jnp.maximum(h, 0.0)
    y = jnp.einsum("e,etf->tf", g, h)
    y = jax.lax.psum(y, "model")
    return jnp.tanh(y * 0.125) + x

def moe_ref(x, w, g):
    h = jnp.einsum("td,edf->etf", x, w)
    h = jnp.maximum(h, 0.0)
    y = jnp.einsum("e,etf->tf", g, h)
    return jnp.tanh(y * 0.125) + x

MOE_SPECS = dict(in_specs=(P("data", None), P("model", None, None),
                           P("model")),
                 out_specs=P("data", None))

def ints(*shape):
    return np.asarray(rng.integers(-2, 3, shape), np.float32)
"""

_CHILD_FP32 = _CHILD_COMMON + r"""
for name, fn, ref_fn, specs, args in [
    ("transformer", block, block_ref, BLOCK_SPECS,
     (ints(8, 16), ints(16, 32), ints(32, 16))),
    ("moe", moe, moe_ref, MOE_SPECS,
     (ints(8, 8), ints(4, 8, 8), ints(4))),
]:
    sf = stitched_jit(fn, mesh=mesh, **specs)
    out = sf(*args)
    rep = sf.report(*args)
    assert rep.sharded and rep.n_collective >= 1, (name, rep)
    assert rep.mesh_axes == (("data", 4), ("model", 2)), rep.mesh_axes

    # sharded XLA reference: same per-shard body, no stitching
    xla = jax.jit(shard_map(fn, mesh=mesh, check_vma=False, **specs))
    # single-device stitched + plain references (global formulation)
    single = stitched_jit(ref_fn)
    for tag, want in [("xla-sharded", xla(*args)),
                      ("stitched-1dev", single(*args)),
                      ("plain", ref_fn(*map(jnp.asarray, args)))]:
        got, want = np.asarray(out), np.asarray(want)
        assert got.shape == want.shape, (name, tag, got.shape, want.shape)
        assert np.array_equal(got, want), (
            name, tag, float(np.max(np.abs(got - want))))

    # the sharded plan keys differently from the mesh-free plan
    assert rep.signature != single.report(*args).signature, name
    print("OK", name)
print("DONE fp32")
"""

_CHILD_BF16 = _CHILD_COMMON + r"""
args = (ints(8, 16).astype(jnp.bfloat16),
        ints(16, 32).astype(jnp.bfloat16),
        ints(32, 16).astype(jnp.bfloat16))
sf = stitched_jit(block, mesh=mesh, **BLOCK_SPECS)
out = np.asarray(sf(*args), np.float32)
xla = jax.jit(shard_map(block, mesh=mesh, check_vma=False, **BLOCK_SPECS))
want = np.asarray(xla(*args), np.float32)
np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)
print("DONE bf16")
"""


def test_sharded_numerics_match_references_fp32(run_sharded):
    out = run_sharded(_CHILD_FP32)
    assert "OK transformer" in out and "OK moe" in out
    assert "DONE fp32" in out


def test_sharded_numerics_bf16_banded(run_sharded):
    assert "DONE bf16" in run_sharded(_CHILD_BF16)
