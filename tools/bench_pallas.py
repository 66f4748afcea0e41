"""Compile-and-compare: the pallas VMEM-resident kernel (ops/pallas_sgd.py)
against the XLA-compiled SGD inner loop at the flagship shape.

On the chip (``chiprun -- python tools/bench_pallas.py``) the kernel is
compiled by Mosaic (``interpret=False``) — the test suite only ever runs it
interpreted — and its weights are compared with the XLA loop's. Each
implementation is then timed over CHAINED data-dependent steps closed by
``block_until_ready``. ``--interpret`` asks for the Pallas interpreter
instead (what a CPU rehearsal needs); the kernel never picks it by itself.
Every line names the device it ran on.

Usage: python tools/bench_pallas.py [--rows 2048] [--features 1024]
       [--iters 50] [--chain 32] [--interpret]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    args = list(sys.argv[1:] if argv is None else argv)
    rows, features, iters, chain, interpret = 2048, 1024, 50, 32, False
    i = 0
    while i < len(args):
        if args[i] == "--interpret":
            interpret = True; i += 1
        elif args[i] == "--rows":
            rows = int(args[i + 1]); i += 2
        elif args[i] == "--features":
            features = int(args[i + 1]); i += 2
        elif args[i] == "--iters":
            iters = int(args[i + 1]); i += 2
        elif args[i] == "--chain":
            chain = int(args[i + 1]); i += 2
        else:
            raise SystemExit(f"unknown flag {args[i]!r}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from twtml_tpu.ops import pallas_sgd

    rng = np.random.default_rng(0)
    x = np.zeros((rows, features), np.float32)
    idx = rng.integers(0, features - 4, size=(rows, 40))
    for r in range(rows):
        np.add.at(x[r], idx[r], 1.0)
    x[:, -4:] = rng.normal(size=(rows, 4)).astype(np.float32) * 0.1
    X = jnp.asarray(x)
    y = jnp.asarray(rng.uniform(100, 1000, size=(rows,)).astype(np.float32))
    m = jnp.ones((rows,), jnp.float32)
    w0 = jnp.zeros((features,), jnp.float32)

    def xla_loop(X, y, m, w):
        # drive the CANONICAL inner loop (models/sgd.py is the one place
        # the parity-critical semantics live) so the comparison can never
        # drift from the shipped path
        from twtml_tpu.models.sgd import sampling_key, sgd_inner_loop

        def grad_and_count(wv, sel):
            residual = (X @ wv - y) * sel
            return X.T @ residual, jnp.sum(sel)

        return sgd_inner_loop(
            w,
            num_iterations=iters,
            step_size=0.005,
            mini_batch_fraction=1.0,
            l2_reg=0.0,
            convergence_tol=0.001,
            mask=m,
            sample_key=sampling_key(None, 1.0),
            grad_and_count=grad_and_count,
        )

    xla_fn = jax.jit(xla_loop)
    pal_fn = jax.jit(
        lambda X, y, m, w: pallas_sgd.fused_dense_sgd(
            X, y, m, w, num_iterations=iters, step_size=0.005,
            interpret=interpret,
        )[0]
    )

    def chained(fn) -> float:
        """Seconds per step over `chain` data-dependent dispatches, best of
        3, closed by block_until_ready (compile excluded)."""
        fn(X, y, m, w0).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            w = w0
            for _ in range(chain):
                w = fn(X, y, m, w)  # w chains: no overlap, honest total
            w.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / chain)
        return best

    from twtml_tpu.utils.backend import device_identity

    device = device_identity()
    t_xla = chained(xla_fn)
    t_pal = chained(pal_fn)
    w_xla, w_pal = xla_fn(X, y, m, w0), pal_fn(X, y, m, w0)
    diff = float(jnp.max(jnp.abs(w_xla - w_pal)))
    scale = float(jnp.max(jnp.abs(w_xla)))
    for name, t in (("xla_fori_loop", t_xla), ("pallas_vmem_resident", t_pal)):
        print(json.dumps({
            "impl": name,
            "ms_per_step": round(t * 1000, 4),
            "rows": rows, "features": features, "iters": iters,
            "chain": chain, "interpret": interpret and name != "xla_fori_loop",
            "device": device,
        }))
    print(json.dumps({
        "max_abs_weight_diff": diff, "max_abs_weight": scale,
        "relative": diff / max(scale, 1e-30), "device": device,
    }))


if __name__ == "__main__":
    main()
