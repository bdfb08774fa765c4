#!/usr/bin/env python3
"""The experiment behind ``memory_peak_bytes`` (meter.py), on the chip:

    python3 benchmarks/memcheck.py

What does ``memory_stats()`` count where? One JSON line a phase:

1. ``live``: a 5 GB array is made and dropped. It shows in ``bytes_in_use``
   and ``peak_bytes_in_use``; ``bytes_reserved`` stays 0.
2. ``carry``: a jitted while-loop carries a [10, 40M] float32 buffer, as an
   L-BFGS history is carried (1.6 GB; XLA's own ``temp_size_in_bytes`` is
   printed beside it). ``memory_stats()`` is read every 20 ms while it runs:
   the largest ``bytes_in_use``, the largest ``bytes_reserved`` and the
   largest sum that one reading showed, against ``peak_bytes_in_use``.
3. ``disjoint``: the same loop beside a live array that leaves room for the
   program's temporaries (it runs), then beside one that leaves room for its
   arguments and results alone (it has to fail for want of memory). Where it
   does, the reserved bytes are held on the chip apart from ``bytes_in_use``,
   and the bytes held at one instant are their sum.

Nothing here is a benchmark result and the driver never runs it.
"""

from __future__ import annotations

import json
import sys
import threading
import time

DIM = 40_000_000
GB = 1_000_000_000


def main() -> int:
    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.stderr.write("memcheck.py: the readings are the chip's; JAX found no TPU\n")
        return 4
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved")

    def stats() -> dict:
        s = device.memory_stats()
        return {k: int(s.get(k, 0)) for k in keys}

    def say(phase: str, **doc) -> None:
        print(json.dumps({"phase": phase, "kind": device.device_kind, **doc}), flush=True)

    limit = int(device.memory_stats()["bytes_limit"])
    live = jnp.ones((5 * GB // 4,), jnp.float32).block_until_ready()
    held = stats()
    del live
    say("live", bytes_limit=limit, while_held=held, after=stats())

    @jax.jit
    def carry(w):
        def body(c):
            i, w, h = c
            h = h.at[jnp.mod(i, 10)].set(w * 1.5)
            return i + 1, w + h[jnp.mod(i + 3, 10)] * 0.1, h

        init = (0, w, jnp.zeros((10, DIM), jnp.float32))
        return jax.lax.while_loop(lambda c: c[0] < 1500, body, init)[1]

    w = jnp.ones((DIM,), jnp.float32)
    analysis = carry.lower(w).compile().memory_analysis()
    carry(w).block_until_ready()
    samples, stop = [], threading.Event()

    def poll() -> None:
        while not stop.wait(0.02):
            samples.append(stats())

    before = stats()
    thread = threading.Thread(target=poll)
    thread.start()
    t0 = time.perf_counter()
    carry(w).block_until_ready()
    ran = time.perf_counter() - t0
    stop.set()
    thread.join()
    say("carry", ran_s=ran, samples=len(samples),
        temp_size_in_bytes=int(analysis.temp_size_in_bytes),
        before=before, after=stats(),
        max_bytes_in_use=max(s["bytes_in_use"] for s in samples),
        max_bytes_reserved=max(s["bytes_reserved"] for s in samples),
        max_instant_sum=max(s["bytes_in_use"] + s["bytes_reserved"] for s in samples))

    temp = int(analysis.temp_size_in_bytes)
    for name, spare in (("room_for_temporaries", temp + GB), ("room_for_arguments_only", GB)):
        base = stats()["bytes_in_use"]
        ballast = jnp.ones(((limit - base - spare) // 4,), jnp.float32).block_until_ready()
        try:
            carry(w).block_until_ready()
            outcome = "ran"
        except Exception as e:  # noqa: BLE001 - the failure is the reading
            outcome = f"failed: {type(e).__name__}: {str(e)[:300]}"
        say("disjoint", case=name, ballast_bytes=int(ballast.size) * 4, spare_bytes=spare,
            outcome=outcome, stats=stats())
        del ballast
    return 0


if __name__ == "__main__":
    sys.exit(main())
