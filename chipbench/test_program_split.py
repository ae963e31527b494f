"""The split of the decode wave's device idle time into dispatch, gaps
between ops inside the program, and readback (``benchlib.program``), on
a small recorded trace that holds the program's own spans nested in the
harness's and a TPU ``XLA Modules`` line; and the harness's reduction
(``benchlib.trace``) reading that trace as it read the trace without
them."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import program, trace  # noqa: E402

MS = 1e-3
# The harness's spans and the device's ops are those of the harness's
# own small trace (``test_chipbench_harness.XSPACE``), the custom call
# named as a stitched kernel now is.  Line clocks start at 1 ms, so an
# event at t ms has offset (t - 1) ms.  Device: ops 1-3 and 2-4 ms (the
# stitched kernel) and 7-8 ms; wave programs 1.0-4.2 and 6.8-8.3 ms, an
# argmax 4.3-4.4 ms.  Host: decode_wave 1-5 ms, host_loop 5-6.5 ms,
# decode_wave 6.5-9 ms, each wave's program spans inside.  Any shift of
# the device clock in [0, 1] ms puts every op inside the calls; the
# reduction takes 0.5 ms, so the device events land at t + 0.5 ms.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3200000000 }
    events { metadata_id: 4 offset_ps: 3300000000 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 5800000000 duration_ps: 1500000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2
    name: "%stitch_onepass_3.1 = f32[8]{0} custom-call(f32[8]{0} %x)" } }
  event_metadata { key: 3 value { id: 3
    name: "jit_stitched_decode_wave(1640922085)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_argmax(77)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 1500000000 }
    events { metadata_id: 1 offset_ps: 5500000000 duration_ps: 2500000000 }
    events { metadata_id: 3 offset_ps: 4100000000 duration_ps: 10000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 3900000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 6 offset_ps: 50000000 duration_ps: 150000000 }
    events { metadata_id: 7 offset_ps: 50000000 duration_ps: 100000000 }
    events { metadata_id: 8 offset_ps: 150000000 duration_ps: 50000000 }
    events { metadata_id: 9 offset_ps: 200000000 duration_ps: 3400000000 }
    events { metadata_id: 10 offset_ps: 3600000000 duration_ps: 300000000 }
    events { metadata_id: 4 offset_ps: 5600000000 duration_ps: 2300000000 }
    events { metadata_id: 5 offset_ps: 5600000000 duration_ps: 50000000 }
    events { metadata_id: 6 offset_ps: 5650000000 duration_ps: 150000000 }
    events { metadata_id: 7 offset_ps: 5650000000 duration_ps: 100000000 }
    events { metadata_id: 8 offset_ps: 5750000000 duration_ps: 50000000 }
    events { metadata_id: 9 offset_ps: 5800000000 duration_ps: 1900000000 }
    events { metadata_id: 10 offset_ps: 7700000000 duration_ps: 200000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "decode_wave" } }
  event_metadata { key: 2 value { id: 2 name: "host_loop" } }
  event_metadata { key: 3 value { id: 3 name: "some_library_event" } }
  event_metadata { key: 4 value { id: 4 name: "serve.wave" } }
  event_metadata { key: 5 value { id: 5 name: "wave.inputs" } }
  event_metadata { key: 6 value { id: 6 name: "stitch.call" } }
  event_metadata { key: 7 value { id: 7 name: "stitch.lookup" } }
  event_metadata { key: 8 value { id: 8 name: "stitch.launch" } }
  event_metadata { key: 9 value { id: 9 name: "wave.sample" } }
  event_metadata { key: 10 value { id: 10 name: "wave.retire" } }
}
"""


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(XSPACE)


def approx_items(pairs):
    return {k: pytest.approx(v) for k, v in pairs}


def test_the_harness_reduction_ignores_the_program_events(pd):
    """Busy time, device time inside spans, span counts, top ops and
    idle by span are those of the trace without the program's events."""
    tr = trace.from_profile(pd)
    assert [s[0] for s in tr.spans] == ["decode_wave", "host_loop",
                                        "decode_wave"]
    red = trace.reduce(tr)
    assert red.offset_s == pytest.approx(0.5 * MS)
    assert red.busy_s == pytest.approx(4 * MS)
    assert red.span_busy_s["decode_wave"] == pytest.approx(4 * MS)
    assert red.span_busy_s["host_loop"] == pytest.approx(0.0)
    assert red.span_count == {"decode_wave": 2, "host_loop": 1}
    assert red.top_ops == [["fusion.1", pytest.approx(3 * MS)],
                           ["stitch_onepass_3.1", pytest.approx(2 * MS)]]
    assert approx_items(red.idle_by_span) == \
        {"host_loop": 3 * MS, "decode_wave": 1 * MS}


def test_program_events_are_read(pd):
    ev = program.events(pd)
    assert [s[0] for s in ev.spans[:6]] == [
        "serve.wave", "wave.inputs", "stitch.call", "stitch.lookup",
        "stitch.launch", "wave.sample"]
    assert len(ev.spans) == 14
    assert [m[0] for m in ev.modules["/device:TPU:0"]] == [
        "jit_stitched_decode_wave", "jit_argmax",
        "jit_stitched_decode_wave"]


def test_each_wave_splits_into_dispatch_op_gaps_and_readback(pd):
    sp = program.split(trace.from_profile(pd), program.events(pd))
    assert sp.missing == 0 and sp.trusted
    # wave 1: span 1.0-4.9, program 1.5-4.7 over ops busy 1.5-4.5:
    # dispatch 0.5 ms, 0.2 ms after the last op, readback 4.7-4.9.
    # Wave 2: span 6.6-8.9, program 7.3-8.8 over the op at 7.5-8.5.
    got = [(w.dispatch_s, w.op_gap_s, w.readback_s, w.ops, w.busy_s,
            w.stitched_s) for w in sp.waves]
    assert got == [pytest.approx((0.5 * MS, 0.2 * MS, 0.2 * MS, 2, 3 * MS,
                                  2 * MS)),
                   pytest.approx((0.7 * MS, 0.5 * MS, 0.1 * MS, 1, 1 * MS,
                                  0.0))]
    assert sp.median("dispatch_s") == pytest.approx(0.6 * MS)
    assert sp.median("op_gap_s") == pytest.approx(0.35 * MS)
    assert sp.median("readback_s") == pytest.approx(0.15 * MS)
    assert sp.median("ops") == pytest.approx(1.5)
    assert sp.stitched_pct() == pytest.approx(50.0)
    # the three parts are the device idle inside each wave's span
    for w, (ws, we) in zip(sp.waves, [(1.0, 4.9), (6.6, 8.9)]):
        busy = (3.0 if ws < 5 else 1.0) * MS
        assert w.dispatch_s + w.op_gap_s + w.readback_s == \
            pytest.approx((we - ws) * MS - busy)


def test_idle_by_innermost_program_span(pd):
    sp = program.split(trace.from_profile(pd), program.events(pd))
    # idle 1-1.5, 4.5-7.5 and 8.5-9 ms, piece by piece under the
    # innermost span: wave.sample 0.3 + 0.1 + 0.7 + 0.2, each wave's
    # inputs, lookup and launch, retire 0.3 + 0.2, the harness's
    # decode_wave outside serve.wave 3 x 0.1
    assert approx_items(sp.gaps) == {
        "wave.sample": 1.3 * MS, "host_loop": 1.5 * MS,
        "wave.retire": 0.5 * MS, "decode_wave": 0.3 * MS,
        "stitch.lookup": 0.2 * MS, "wave.inputs": 0.1 * MS,
        "stitch.launch": 0.1 * MS}
    assert sum(v for _, v in sp.gaps) == pytest.approx(4 * MS)


def test_waves_without_their_program_are_not_trusted(pd):
    tr = trace.from_profile(pd)
    ev = program.events(pd)
    ev.modules = {dev: [m for m in mods if m[1] > 5 * MS]  # wave 2's
                  for dev, mods in ev.modules.items()}
    sp = program.split(tr, ev)
    assert (len(sp.waves), sp.missing) == (1, 1)
    assert not sp.trusted
    assert sp.median("dispatch_s") is None and sp.stitched_pct() is None


def test_untraced_host_time_is_named():
    # ops at 1-1.5 and 3-3.2 ms sit best unshifted against the one call
    # span 0.5-2 ms; idle in the window 0-4 ms outside it is untraced
    tr = trace.Trace(ops={"/device:TPU:0": [("a", 1 * MS, 1.5 * MS),
                                            ("b", 3 * MS, 3.2 * MS)]},
                     spans=[("decode_wave", 0.5 * MS, 2 * MS)])
    sp = program.split(tr, program.Events(), 0.0, 4 * MS)
    assert approx_items(sp.gaps) == {"decode_wave": 1 * MS,
                                     "untraced_host": 2.3 * MS}
    assert sp.waves == [] and not sp.trusted
