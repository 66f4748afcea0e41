"""Iterations the primal learner ran a batch before its converged-freeze:
the mean ``iterations`` of the span file's ``primal`` instants (one a
delivered batch; ``primal_loop_ms_per_batch`` finds them). The program
counts them inside the step (``models/sgd.sgd_inner_loop``'s
``count_iterations``: a round that starts unfrozen ran, as MLlib's loop
breaks AFTER the round that met the tolerance) and fetches the count with
the batch's statistics; nothing recomputes it. ``numIterations`` (50) where
no batch converges early; the device runs all 50 rounds either way (a
``fori_loop``), so this is what a truly early exit could save, not what
the step took — and so, as the program stands, this counter moves NO
end-to-end metric (``moves`` names the rate because the manifest wants a
name: it is the rate an early exit would move, PERF.md section 3). Over
every batch of the window run, as ``gram_fast_plane_share`` is. None
without the instants."""

from benchmark.layer_metrics import primal_loop_ms_per_batch as primal


def read(art):
    seen = primal.instants()
    if not seen:
        return None
    return sum(a["iterations"] for a in seen) / len(seen)
