"""The dispatch-table key of ``StitchedFunction``.

A strongly typed array leaf is keyed by its ``shape`` and ``dtype``
attributes, the dtype named once per ``(dtype, x64)`` by
``jnp.result_type``; the key per leaf is the one ``np.shape`` and
``jnp.result_type`` give on the value, which every other leaf (Python
scalars, weakly typed arrays, PRNG keys) still uses and counts in
``key_fallback_leaves``.  The argument tree is part of the key, and so
is the ambient mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import stitch
from repro.core.stitch import StitchedFunction
from repro.dist.partitioning import use_mesh
from repro.models import build_model
from repro.serving import ContinuousBatcher

rng = np.random.default_rng(14)


def _value_key(flat) -> tuple:
    """The key per leaf, computed on each value as the slow path does."""
    return tuple((tuple(np.shape(a)), str(jnp.result_type(a)))
                 for a in flat)


def _key(sf, *args):
    flat, tree = jax.tree_util.tree_flatten((args, {}))
    return sf._signature(flat, tree), flat


def _ints(dtype, shape=(8, 128)):
    # small integers: exact in every dtype below, so results compare equal
    return np.asarray(rng.integers(-4, 5, shape)).astype(dtype)


def _affine(x, y):
    return (x + y) * 2 - y


DTYPE_CASES = {
    # x64 off: a float64 leaf keys as float32 and shares its instance
    "float64_vs_float32": ((_ints(np.float64), _ints(np.float64)),
                           (jnp.asarray(_ints(np.float32)),
                            jnp.asarray(_ints(np.float32)))),
    "bfloat16": ((jnp.asarray(_ints(np.float32), jnp.bfloat16),) * 2,
                 (jnp.asarray(_ints(np.float32), jnp.bfloat16),) * 2),
    "int32": ((jnp.asarray(_ints(np.int32)), jnp.asarray(_ints(np.int32))),
              (jnp.asarray(_ints(np.int32)), _ints(np.int32))),
    "python_float": ((jnp.asarray(_ints(np.float32)), 1.5),
                     (jnp.asarray(_ints(np.float32)), -0.5)),
    "python_int": ((jnp.asarray(_ints(np.int32)), 3),
                   (jnp.asarray(_ints(np.int32)), -2)),
    "python_bool": ((jnp.asarray(_ints(np.int32)), True),
                    (jnp.asarray(_ints(np.int32)), False)),
}


@pytest.mark.parametrize("case", sorted(DTYPE_CASES))
def test_key_per_leaf_reaches_one_instance(case):
    first, second = DTYPE_CASES[case]
    sf = StitchedFunction(_affine)
    for args in (first, second):
        key, flat = _key(sf, *args)
        assert key[1:] == _value_key(flat)
        np.testing.assert_array_equal(
            np.asarray(sf(*args), np.float32),
            np.asarray(_affine(*(jnp.asarray(a) for a in args)),
                       np.float32))
    assert sf.n_compiled == 1
    assert _key(sf, *first)[0] == _key(sf, *second)[0]


def _by_structure(t):
    if isinstance(t, dict):
        return t["x"] - t["y"]
    return t[0] + t[1]


def test_argument_tree_keys_its_own_instance():
    a = jnp.asarray(_ints(np.float32))
    b = jnp.asarray(_ints(np.float32))
    sf = StitchedFunction(_by_structure)
    as_tuple = sf((a, b))
    as_dict = sf({"x": a, "y": b})
    assert sf.n_compiled == 2
    np.testing.assert_array_equal(np.asarray(as_tuple), np.asarray(a + b))
    np.testing.assert_array_equal(np.asarray(as_dict), np.asarray(a - b))


def _first_and_last(xs):
    return xs[0] * 2.0 + xs[-1]


def test_wide_call_never_asks_result_type(monkeypatch):
    xs = [jnp.asarray(_ints(np.float32, (8, 128))) for _ in range(400)]
    xs += [jnp.asarray(_ints(np.float32, (8, 128)), jnp.bfloat16)
           for _ in range(46)]
    sf = StitchedFunction(_first_and_last)
    sf(xs)                                      # warm-up: compiles
    calls = []
    real = jnp.result_type

    def counting(*args, **kwargs):
        calls.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(stitch.jnp, "result_type", counting)
    out = sf(xs)
    assert calls == []
    assert sf.key_fallback_leaves == 0 and sf.n_compiled == 1
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(xs[0] * 2.0 + xs[-1]))


@pytest.mark.parametrize("leaf", ["prng_key", "python_scalar",
                                  "weak_array"])
def test_other_leaves_take_the_value_key(leaf):
    # a weakly typed array's name comes from its value: weak bfloat16,
    # for one, is named float32
    value = {"prng_key": jax.random.key(3), "python_scalar": 0.25,
             "weak_array": jnp.asarray(2.0)}[leaf]
    sf = StitchedFunction(_affine)
    x = jnp.asarray(_ints(np.float32))
    key, flat = _key(sf, x, value)
    assert key[1:] == _value_key(flat)
    assert sf.key_fallback_leaves == 1
    _key(sf, x, value)
    assert sf.key_fallback_leaves == 2


def test_dtype_names_follow_an_x64_switch():
    sf = StitchedFunction(_affine)
    x = _ints(np.float64)
    off, _ = _key(sf, x, x)
    with jax.enable_x64(True):
        on, flat = _key(sf, x, x)
        assert on[1:] == _value_key(flat)
    assert off[1][1] == "float32" and on[1][1] == "float64"
    assert _key(sf, x, x)[0] == off


class _FakeMesh:
    """Shape-only mesh: the key reads the mesh's axes and sizes."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def test_mesh_keys_the_dispatch_table():
    sf = StitchedFunction(_affine)
    x = jnp.asarray(_ints(np.float32))
    free, _ = _key(sf, x, x)
    with use_mesh(_FakeMesh(data=4, model=2)):
        sharded, _ = _key(sf, x, x)
    assert sharded != free
    assert sharded[:-1] == free
    assert sharded[-1] == (("data", 4), ("model", 2))


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "mamba2-370m"])
def test_served_calls_take_the_attribute_path(arch):
    cfg = get_config(arch).reduced()
    mdl = build_model(cfg, fusion_mode="xla")
    params = mdl.init(jax.random.PRNGKey(0))
    server = ContinuousBatcher(mdl, params, n_slots=2, max_len=32,
                               stitched=True)
    for n in (5, 9):
        server.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                      max_new=3)
    server.run()
    assert isinstance(server._decode_wave, StitchedFunction)
    assert server._decode_wave.key_fallback_leaves == 0
    assert server._prefill.key_fallback_leaves == 0
