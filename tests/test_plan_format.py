"""The plan cache's one entry format.

An entry loads only when it is stamped ``FORMAT_VERSION``.  Any other
stamp -- one an older layout wrote, or a malformed one -- is a miss:
the graph re-plans and the fresh entry is stored over the stale one.
The optional ``anchors``, recompute and ``mesh`` records are fields of
that one layout.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import Hardware, StitchedFunction
from repro.core.plan_cache import FORMAT_VERSION, entry_checksum, \
    entry_partition_source
from repro.launch.mesh import make_test_mesh

rng = np.random.default_rng(17)


def _chain(x):
    """Memory-intensive only: no anchor, one stitched group."""
    h = x * 2.0 + 1.0
    h = jnp.tanh(h) * x
    return h - jnp.mean(h, axis=-1, keepdims=True)


def _mlp(x, w, r):
    """Prologue -> matmul -> epilogue: one anchored group."""
    h = x * 2.0 + 1.0
    return jnp.tanh(h @ w) + r


def _chain_args():
    return (rng.standard_normal((16, 128)).astype(np.float32),)


def _mlp_args():
    return (rng.standard_normal((64, 32)).astype(np.float32),
            rng.standard_normal((32, 48)).astype(np.float32),
            rng.standard_normal((64, 48)).astype(np.float32))


#: the three layouts the previous release wrote: 5 (anchor-free),
#: 6 (with anchored groups), 7 (with a ``mesh`` record)
_CASES = {
    "anchor_free": (5, _chain, _chain_args, {}),
    "anchored": (6, _mlp, _mlp_args, {}),
    "sharded": (7, _chain, _chain_args,
                {"in_specs": (P(),), "out_specs": (P(),)}),
}


def _path(cache_dir, signature):
    return os.path.join(str(cache_dir), f"{signature}.json")


def _read(cache_dir, signature):
    with open(_path(cache_dir, signature)) as f:
        return json.load(f)


def _restamp(cache_dir, signature, stamp):
    """Rewrite the stored entry's stamp (``None``: drop it) and reseal
    it, as the tree that wrote that stamp would have."""
    entry = _read(cache_dir, signature)
    entry.pop("checksum")
    if stamp is None:
        del entry["format"]
    else:
        entry["format"] = stamp
    entry["checksum"] = entry_checksum(entry)
    with open(_path(cache_dir, signature), "w") as f:
        json.dump(entry, f)


def _assert_replans_and_rewrites(fn, args, kw, cache_dir, rep1):
    sf = StitchedFunction(fn, plan_cache=str(cache_dir), **kw)
    rep = sf.report(*args)
    assert rep.signature == rep1.signature
    assert not rep.plan_cache_hit
    assert rep.partition_candidates >= 1      # the stitcher searched anew
    assert rep.n_anchored == rep1.n_anchored
    # a miss, not corruption: nothing was quarantined
    assert not os.path.exists(os.path.join(str(cache_dir), "quarantine"))
    assert _read(cache_dir, rep.signature)["format"] == FORMAT_VERSION
    ref = fn(*(jnp.asarray(a) for a in args))
    np.testing.assert_allclose(np.asarray(sf(*args)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", sorted(_CASES))
def test_older_stamp_is_a_miss_and_is_rewritten(case, tmp_path):
    stamp, fn, make_args, kw = _CASES[case]
    if kw:
        kw = dict(kw, mesh=make_test_mesh(1))
    args = make_args()
    rep1 = StitchedFunction(fn, plan_cache=str(tmp_path), **kw).report(*args)
    entry = _read(tmp_path, rep1.signature)
    assert entry["format"] == FORMAT_VERSION
    assert any(g.get("anchors") for g in entry["groups"]) \
        == (case == "anchored")
    assert ("mesh" in entry) == (case == "sharded")

    _restamp(tmp_path, rep1.signature, stamp)
    _assert_replans_and_rewrites(fn, args, kw, tmp_path, rep1)


@pytest.mark.parametrize("stamp", [None, True, "8", 99, 8.0],
                         ids=["missing", "bool", "string", "future",
                              "float"])
def test_malformed_stamp_is_a_miss(stamp, tmp_path):
    args = _chain_args()
    rep1 = StitchedFunction(_chain, plan_cache=str(tmp_path)).report(*args)
    _restamp(tmp_path, rep1.signature, stamp)
    _assert_replans_and_rewrites(_chain, args, {}, tmp_path, rep1)


def _anchored_then_fanout(a, w, r, g):
    """An anchored matmul block, then (past an opaque sort) a six-branch
    fan-out whose live set only fits VMEM under recompute."""
    x = jnp.sort(jnp.tanh((a * 2.0 + 1.0) @ w) + r, axis=-1)
    t = x * g + 1.0
    us = [jnp.tanh(t * (0.1 * (i + 1))) for i in range(6)]
    acc = x
    for u in us:
        acc = acc + u
    for u in us:
        acc = acc * (u + 0.5)
    return acc * jnp.mean(acc, axis=-1, keepdims=True)


def test_anchored_entry_with_recompute_pin_reloads_without_search(tmp_path):
    hw = Hardware(vmem_bytes=256 * 1024)
    args = (rng.standard_normal((16, 32)).astype(np.float32),
            rng.standard_normal((32, 512)).astype(np.float32),
            rng.standard_normal((16, 512)).astype(np.float32),
            (np.abs(rng.standard_normal(512)) + 0.5).astype(np.float32))
    sf1 = StitchedFunction(_anchored_then_fanout, hw=hw,
                           plan_cache=str(tmp_path))
    rep1 = sf1.report(*args)
    assert rep1.n_anchored >= 1 and rep1.n_recomputed >= 1
    entry = _read(tmp_path, rep1.signature)
    assert entry["format"] == FORMAT_VERSION
    assert any(g.get("anchors") for g in entry["groups"])
    assert any(p.get("recompute") for p in entry["patterns"])

    sf2 = StitchedFunction(_anchored_then_fanout, hw=hw,
                           plan_cache=str(tmp_path))
    rep2 = sf2.report(*args)
    assert rep2.plan_cache_hit
    assert rep2.partition_candidates == 0     # no search
    assert rep2.groups == rep1.groups
    assert rep2.n_anchored == rep1.n_anchored
    assert rep2.n_recomputed == rep1.n_recomputed
    np.testing.assert_array_equal(np.asarray(sf2(*args)),
                                  np.asarray(sf1(*args)))


def test_partition_source_reads_the_marker_alone():
    assert entry_partition_source({"partition_source": "measured"}) \
        == "measured"
    assert entry_partition_source({"format": FORMAT_VERSION,
                                   "partition_source": "model"}) == "model"
    assert entry_partition_source({"format": FORMAT_VERSION}) == "model"
