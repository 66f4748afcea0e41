"""chip_smoke.py, tiny, on the CPU test mesh — and the no-chip refusals.

The smoke's phases take their sizes as arguments so tier-1 can drive the
same control flow in seconds (real entry points, real HTTP, a CPU
"reference" that is the same backend here, so deviations are exactly 0);
``main()`` itself has no way to pass without a chip, which is asserted
through a subprocess, like ``--backend tpu`` and ``benchmark.run`` below.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from twtml_tpu.telemetry import metrics as _metrics  # noqa: E402


@pytest.fixture(autouse=True)
def _clean():
    _metrics.reset_for_tests()
    yield
    _metrics.reset_for_tests()


def test_phases_tiny_on_cpu(tmp_path, capsys):
    """devices -> native -> train (dense) -> train (Gram) -> serve ->
    multichip on a 4-device virtual mesh: every phase the chip run makes,
    through the same entry points, at a size that takes seconds."""
    out = str(tmp_path)
    assert chip_smoke.phase_devices()["platform"] == "cpu"
    live = chip_smoke.phase_native()
    assert "featurize_wire" in live["symbols"] and live["stamp"]["host"]

    one = chip_smoke.phase_train(
        "tiny", out, backend="cpu", num_text_features=1000, batch=64,
        n_batches=3, seed=7,
    )
    assert one["totals"]["count"] == one["kept"] == 192
    assert one["totals"]["device"]["platform"] == "cpu"
    assert one["stats"].count == 192 and one["step"] == 3

    chip_smoke.phase_train(
        "tinygram", out, backend="cpu", num_text_features=16384, batch=64,
        n_batches=2, seed=11, extra=("--l2Reg", "0.1"),
    )

    served = chip_smoke.phase_serve(
        one["ckpt"], backend="cpu", expect_step=3, row_counts=(1, 3, 9),
    )
    assert len(served["predictions"]) == 13
    assert served["view"]["requests"] == 3 and served["view"]["rows"] == 13

    multi = chip_smoke.phase_multichip(
        out, one, backend="cpu", num_text_features=1000, batch=64,
        n_batches=3, n_devices=4, master="local[4]",
    )
    assert multi["totals"]["batches"] == 3
    assert multi["totals"]["device_span"] == {"weights": 4, "batch": 4}
    assert "device_span" not in one["totals"]  # single-device model
    text = capsys.readouterr().out
    assert "max|dw|/max|w| = 0.000e+00" in text  # same backend: exact
    assert "multichip: OK — 4 devices" in text


def test_clock_phase_refuses_unknown_kind_and_impossible_rate():
    with pytest.raises(RuntimeError, match="not in the peak table"):
        chip_smoke.phase_clock(n=128)  # 'cpu' has no row in the table
    rec = chip_smoke.phase_clock(n=128, peaks={"cpu": 1e9})
    assert rec["t_block_s"] > 0 and rec["tflops"] > 0
    # a sync that does not wait implies a rate above the peak: refused
    with pytest.raises(RuntimeError, match="does not wait"):
        chip_smoke.phase_clock(n=128, peaks={"cpu": 1e-9})


def test_a_swallowed_publish_fails_the_run_check():
    run = {
        "totals": {"count": 10, "batches": 1, "device": {"platform": "cpu"}},
        "batches": [{"count": 10, "batch": 10, "mse": 1.0, "t": 0.0}],
        "stats": type("S", (), {"count": 0, "batch": 0})(),  # never published
        "weights": [1.0], "step": 1,
    }
    with pytest.raises(RuntimeError, match="/api/stats"):
        chip_smoke._check_run("t", run, platform="cpu", kept=10, n_batches=1)


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
    )


def test_main_fails_without_a_chip_and_names_the_platform():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_backend_tpu_refuses_a_cpu_only_process():
    proc = _run([
        "-m", "twtml_tpu.apps.linear_regression", "--backend", "tpu",
        "--source", "replay",
        "--replayFile", os.path.join(REPO, "tests", "data", "tweets.jsonl"),
        "--seconds", "0", "--twtweb", chip_smoke.CLOSED,
        "--lightning", chip_smoke.CLOSED,
    ])
    assert proc.returncode != 0
    assert "--backend tpu requested" in proc.stderr and "'cpu'" in proc.stderr
    assert "count:" not in proc.stdout  # it trained nothing


def test_benchmark_exits_nonzero_with_no_metric_without_a_chip():
    # no chip, no number: the benchmark's own entry point, in a CPU-only
    # process, must stop at harness.require_device — neither a CPU rate nor
    # a zero under any metric's name
    proc = _run(["-m", "benchmark.run", "--workload", "hash2e18-trimmed",
                 "--seed", "1", "--seconds", "1"])
    assert proc.returncode != 0
    assert "needs 1 TPU chip(s)" in proc.stderr and "'cpu'" in proc.stderr
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    for line in (proc.stdout + proc.stderr).splitlines():
        assert not any(n in line for n in names), line


def test_mesh_devices_follow_the_platform_the_run_record_names(monkeypatch):
    """The smoke's CPU reference runs inside a process whose first jax
    device is the chip, with ``jax_default_device`` pinned to the CPU: the
    identity in the run record, the local[N] cap and the mesh's devices
    must all come from the pinned platform, never from ``jax.devices()``
    (a 'CPU reference' sharded over the chips would agree vacuously)."""
    import collections

    import jax

    import twtml_tpu.parallel as parallel
    from twtml_tpu.apps import common
    from twtml_tpu.config import ConfArguments
    from twtml_tpu.utils import backend

    Dev = collections.namedtuple("Dev", "platform device_kind id")
    fake = {
        None: [Dev("tpu", "TPU v5 lite", i) for i in range(4)],
        "tpu": [Dev("tpu", "TPU v5 lite", i) for i in range(4)],
        "cpu": [Dev("cpu", "cpu", i) for i in range(2)],
    }
    monkeypatch.setattr(jax, "devices", lambda platform=None: fake[platform])
    monkeypatch.setattr(
        parallel, "make_mesh", lambda num_data, devices: list(devices)
    )
    conf = ConfArguments().parse(["--master", "local[*]"])
    prev = jax.config.jax_default_device
    try:
        assert backend.device_identity() == {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 4,
        }
        assert common.build_mesh(conf) == fake["tpu"]
        jax.config.update("jax_default_device", "cpu")
        assert backend.device_identity() == {
            "platform": "cpu", "kind": "cpu", "count": 2,
        }
        assert common.mesh_shape(conf) == 2
        assert common.build_mesh(conf) == fake["cpu"]
    finally:
        jax.config.update("jax_default_device", prev)


# ---------------------------------------------------------------------------
# the compile cache can be placed from outside (utils/backend.py)

def test_compile_cache_env_wins_and_code_sets_nothing(monkeypatch):
    import jax

    from twtml_tpu.utils.backend import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "/sentinel/left-alone")
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/operator")
        size = jax.config.jax_compilation_cache_max_size
        assert configure_compile_cache() == "/placed/by/operator"
        assert jax.config.jax_compilation_cache_dir == "/sentinel/left-alone"
        assert jax.config.jax_compilation_cache_max_size == size
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_default_is_one_fixed_in_checkout_path(tmp_path):
    code = (
        "import jax; from twtml_tpu.utils.backend import "
        "configure_compile_cache as c; print(c()); "
        "print(jax.config.jax_compilation_cache_dir); "
        "assert jax.config.jax_compilation_cache_max_size == 1 << 30"
    )
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    outs = [
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, timeout=120, check=True,
        ).stdout.split()
        for cwd in (REPO, str(tmp_path))  # two processes, two cwds
    ]
    fixed = os.path.join(REPO, ".jax_cache")
    assert outs == [[fixed, fixed], [fixed, fixed]]
