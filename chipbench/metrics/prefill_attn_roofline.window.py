"""Percent of its roofline that the windowed prefill attention kernel
(``flash_attention_window``, its own ``pallas_call`` name) reaches at
the cell's widths and prompt lengths: a side measurement of the kernel
at the cell's shapes, not a reading of the served prefills.

The traced window's reduction keeps only its ten costliest operations
(``trace.Reduced.top_ops``), among which a windowed layer's kernel in a
prefill seldom is, so the kernel's time comes from a trace of its own:
after the window, in the same process and on the same chip, the
program's ``ops.attention`` with the cell's window runs on random
queries, keys and values of one windowed layer's shapes, once per
prompt of one block of the mix (each prompt length as often as its
ladder weight), under the profiler.  The share is the least time of
that work at the chip's peaks (``work_smallthinker.window_prefill_work``:
only the keys inside each query's window, which is what the kernel has
to compute) over the device time of the operations the kernel's name
names.  So a change to how the stitched prefill calls the kernel does
not move it; a reduction that kept each op's time by name would let it
sum the traced prefills' own calls instead.  None where the run is not
traced or the program has no window.
"""
import shutil
import tempfile

from benchlib import trace as trace_mod
from benchlib import work_smallthinker

KERNEL = "flash_attention_window"


def _named(name: str) -> bool:
    return name == KERNEL or name.startswith(KERNEL + ".")


def read(run):
    if run.trace is None or "sliding_window" not in run.model:
        return None
    import jax
    from repro.kernels import ops

    m, mix = run.model, run.mix
    window = m["sliding_window"]
    fn = jax.jit(lambda q, k, v: ops.attention(q, k, v, causal=True,
                                               window=window))
    keys = iter(jax.random.split(jax.random.PRNGKey(0),
                                 3 * len(mix["prompt_lens"])))
    inputs = {}
    for plen in mix["prompt_lens"]:
        heads = (m["n_heads"], m["n_kv_heads"], m["n_kv_heads"])
        inputs[plen] = [jax.random.normal(next(keys),
                                          (1, h, plen, m["head_dim"]))
                        for h in heads]
        jax.block_until_ready(fn(*inputs[plen]))      # compiled untraced
    calls = [plen for plen, n in zip(mix["prompt_lens"],
                                     mix["prompt_weights"])
             for _ in range(n)]
    out = tempfile.mkdtemp(prefix="chipbench-kernel-")
    try:
        jax.profiler.start_trace(out)
        for plen in calls:
            jax.block_until_ready(fn(*inputs[plen]))
        jax.profiler.stop_trace()
        tr = trace_mod.load(out)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    busy = sum(e - s for evs in tr.ops.values() for name, s, e in evs
               if _named(name)) / max(tr.n_devices, 1)
    if busy <= 0:
        return None
    least = 0.0
    for plen in calls:
        flops, nbytes = work_smallthinker.window_prefill_work(m, plen)
        least += max(flops / run.peak["flops_per_s"],
                     nbytes / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / busy
