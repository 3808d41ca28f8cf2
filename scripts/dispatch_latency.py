"""Per-dispatch cost of the round-apply program on the chip.

Times N back-to-back identical apply_batch_compact_jit dispatches (args
already device-resident, one sync at the end) and one tiny no-op program,
separating fixed per-launch latency from compute.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    docs, slots, marks = 2048, 384, 96
    from peritext_tpu.ops.kernel import apply_batch_compact_jit
    from peritext_tpu.ops.packed import empty_docs

    state = jax.device_put(empty_docs(docs, slots, marks, tomb_capacity=slots))

    ki, kd, km, kp = 64, 32, 32, 8
    n = np.full(docs, 4, np.int32)
    counts = tuple(jax.device_put(x) for x in
                   (n, np.zeros(docs, np.int32), np.zeros(docs, np.int32),
                    np.zeros(docs, np.int32)))
    tot = int(n.sum())
    ins = tuple(jax.device_put(np.zeros(tot, np.int32)) for _ in range(3))
    dels = jax.device_put(np.zeros(0, np.int32))
    from peritext_tpu.ops.encode import MARK_COLS
    from peritext_tpu.ops.packed import MAP_STREAM_COLS
    mk = {c: jax.device_put(np.zeros(0, np.int32)) for c in MARK_COLS}
    mp = {c: jax.device_put(np.zeros(0, np.int32)) for c in MAP_STREAM_COLS}

    def one(st):
        return apply_batch_compact_jit(st, counts, ins, dels, mk, mp,
                                       widths=(ki, kd, km, kp))

    st = one(state)
    jax.block_until_ready(st.char)

    for reps in (1, 4, 16, 64):
        t0 = time.perf_counter()
        st = state
        for _ in range(reps):
            st = one(st)
        jax.block_until_ready(st.char)
        dt = time.perf_counter() - t0
        print(f"chained x{reps}: {dt*1e3:8.1f} ms total, "
              f"{dt*1e3/reps:7.2f} ms/dispatch")

    tiny = jax.jit(lambda x: x + 1)
    x = jax.device_put(jnp.zeros(8, jnp.int32))
    jax.block_until_ready(tiny(x))
    for reps in (1, 64):
        t0 = time.perf_counter()
        y = x
        for _ in range(reps):
            y = tiny(y)
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        print(f"tiny    x{reps}: {dt*1e3:8.1f} ms total, "
              f"{dt*1e3/reps:7.2f} ms/dispatch")


if __name__ == "__main__":
    main()
