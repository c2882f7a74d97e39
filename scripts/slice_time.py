#!/usr/bin/env python
"""Time ONE slice of the north-star program under different program
granularities on the real device: (a) one jit over all 254 steps,
(b) K chunked jits, (c) per-step jits chained through HBM. Attribution
tool for composition overhead (layout assignment across step
boundaries). Usage: [GRAN=whole|chunk|step] [CHUNK_STEPS=48] python
scripts/slice_time.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.hbm_probe import load_plan  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tnc_tpu.ops import chunked
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program, index_buffer, slice_indices
    from tnc_tpu.ops.split_complex import apply_step_split, run_steps_split, split_array

    tn, replace, slicing, _ = load_plan()
    sp = build_sliced_program(tn, replace, slicing)
    program = sp.program
    gran = os.environ.get("GRAN", "whole")
    precision = os.environ.get("PRECISION", "float32")
    chunk_steps = int(os.environ.get("CHUNK_STEPS", "48"))

    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind}) gran={gran}", flush=True)

    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    indices = slice_indices(sp.slicing.dims, 0)
    buffers = []
    for arr, info in zip(arrays, sp.slot_slices):
        sl = index_buffer(np, np.asarray(arr), info, indices)
        re, im = split_array(sl)
        buffers.append((jax.device_put(jnp.asarray(re)), jax.device_put(jnp.asarray(im))))

    def timeit(fn, *args):
        t0 = time.monotonic()
        out = fn(*args)
        jax.block_until_ready(out)
        compile_s = time.monotonic() - t0
        times = []
        for _ in range(3):
            t0 = time.monotonic()
            jax.block_until_ready(fn(*args))
            times.append(time.monotonic() - t0)
        return compile_s, float(np.median(times)), out

    if gran == "whole":
        fn = jax.jit(lambda bufs: run_steps_split(jnp, program, list(bufs), precision))
        c, t, _ = timeit(fn, buffers)
        print(f"whole-slice single jit: compile {c:.1f}s, run {t*1e3:.2f} ms")
    elif gran == "chunk":
        chunks = chunked.split_program(program, chunk_steps)
        fns = []
        for ch in chunks:
            def one(ins, _ch=ch):
                state = dict(zip(_ch.in_slots, ins))
                chunked._run_chunk_split(jnp, _ch, state, precision)
                return tuple(state[s] for s in _ch.out_slots)
            fns.append(jax.jit(one))
        state = dict(enumerate(buffers))
        total_c = total_t = 0.0
        for ch, fn in zip(chunks, fns):
            ins = tuple(state[s] for s in ch.in_slots)
            c, t, outs = timeit(fn, ins)
            total_c += c
            total_t += t
            print(f"  chunk({len(ch.steps)} steps): compile {c:.1f}s run {t*1e3:.2f} ms", flush=True)
            for slot, buf in zip(ch.out_slots, outs):
                state[slot] = buf
            for st in ch.steps:
                state.pop(st.rhs, None)
        print(f"chunked total: compile {total_c:.1f}s, run {total_t*1e3:.2f} ms")
    else:  # step granularity, chained through real buffers
        state = dict(enumerate(buffers))
        total_t = 0.0
        for i, st in enumerate(program.steps):
            fn = jax.jit(lambda a, b, _st=st: apply_step_split(jnp, a, b, _st, precision))
            c, t, out = timeit(fn, state[st.lhs], state[st.rhs])
            total_t += t
            state[st.lhs] = out
            del state[st.rhs]
        print(f"per-step chained total: run {total_t*1e3:.2f} ms")


if __name__ == "__main__":
    main()
