"""Law-checker (tools/lawcheck): the repo laws, enforced statically.

Every rule must FIRE on a seeded violation and stay quiet on the blessed
pattern right next to it — a checker that can't catch the violation it was
built for is worse than none (it certifies). Plus the machinery contracts:
suppressions need reasons, the baseline grandfathers by fingerprint, the
--json/exit-code surface is what CI gates on, and — the acceptance
criterion — THIS repo is clean with an EMPTY baseline.
"""

from __future__ import annotations

import json

import pytest

from tools.lawcheck import engine
from tools.lawcheck.rules import all_rules, rule_ids

# a minimal config.py whose parse() registers --foo (documented) — keeps
# TW007 satisfied in mini-repos that aren't exercising it
_MINI_CONFIG = '''
class ConfArguments:
    def parse(self, args):
        flag = args[0]
        if flag == "--foo":
            pass
        return self
'''
_MINI_README = "Use `--foo` to foo.\n"


def mini_repo(tmp_path, files: dict[str, str]):
    """Materialize a fake checkout: default config/docs plus ``files``."""
    defaults = {
        "twtml_tpu/config.py": _MINI_CONFIG,
        "README.md": _MINI_README,
        "SCALING.md": "nothing here\n",
    }
    defaults.update(files)
    for rel, content in defaults.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(content, encoding="utf-8")
    return tmp_path


def run(tmp_path, files: dict[str, str]):
    root = mini_repo(tmp_path, files)
    return engine.run_repo(root=str(root),
                           baseline_path=str(root / "baseline.json"))


def rules_fired(report):
    return sorted({f.rule for f in report.findings})


# ---------------------------------------------------------------------------
# per-rule seeded violations


def test_tw001_fires_on_module_scope_backend_init(tmp_path):
    report = run(tmp_path, {"twtml_tpu/foo.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "DEVICES = jax.devices()\n"
        "ZEROS = jnp.zeros((8,))\n"
        "def fine():\n"
        "    return jax.devices()\n"
    )})
    lines = [f.line for f in report.findings if f.rule == "TW001"]
    assert lines == [3, 4]  # the function body is NOT import-time


def test_tw001_class_body_counts_as_import_time(tmp_path):
    report = run(tmp_path, {"twtml_tpu/foo.py": (
        "import jax.numpy as jnp\n"
        "class C:\n"
        "    TABLE = jnp.arange(4)\n"
    )})
    assert rules_fired(report) == ["TW001"]


def test_tw001_allowlists_conftest_and_backend_helper(tmp_path):
    report = run(tmp_path, {
        "tests/conftest.py": "import jax\nD = jax.devices()\n",
        "twtml_tpu/utils/backend.py": "import jax\nD = jax.devices()\n",
    })
    assert report.findings == []


def test_tw002_fires_outside_seams_quiet_inside(tmp_path):
    bad = (
        "import jax\n"
        "def f(out):\n"
        "    host = jax.device_get(out)\n"
        "    out.block_until_ready()\n"
        "    return host\n"
    )
    report = run(tmp_path, {
        "twtml_tpu/streaming/thing.py": bad,
        "twtml_tpu/apps/common.py": bad,    # the seam implementation
        "tools/soak_x.py": bad,             # tools are out of scope
        "tests/test_x.py": bad,             # tests count fetches themselves
    })
    assert [(f.path, f.line) for f in report.findings] == [
        ("twtml_tpu/streaming/thing.py", 3),
        ("twtml_tpu/streaming/thing.py", 4),
    ]


def test_tw003_fires_on_thread_target_reaching_device_put(tmp_path):
    report = run(tmp_path, {"twtml_tpu/parallel/up.py": (
        "import threading\n"
        "import jax\n"
        "def uploader(x):\n"
        "    return jax.device_put(x)\n"
        "def spawn():\n"
        "    threading.Thread(target=uploader).start()\n"
    )})
    assert [(f.rule, f.line) for f in report.findings] == [("TW003", 6)]


def test_tw003_one_level_deep_and_submit(tmp_path):
    report = run(tmp_path, {"twtml_tpu/parallel/up.py": (
        "import jax\n"
        "def put_helper(x):\n"
        "    return jax.device_put(x)\n"
        "def worker(x):\n"
        "    return put_helper(x)\n"
        "class P:\n"
        "    def go(self, pool, x):\n"
        "        pool.submit(worker, x)\n"
    )})
    assert [(f.rule, f.line) for f in report.findings] == [("TW003", 8)]


def test_tw003_quiet_on_fetch_side_threads(tmp_path):
    report = run(tmp_path, {"twtml_tpu/parallel/down.py": (
        "import jax\n"
        "def fetcher(x):\n"
        "    return jax.device_get(x)\n"
        "def go(pool, out):\n"
        "    pool.submit(fetcher, out)\n"
        "    pool.submit(jax.device_get, out)\n"
    )})
    assert [f for f in report.findings if f.rule == "TW003"] == []


def test_tw004_fires_in_step_code_only(tmp_path):
    scatter = (
        "import jax.numpy as jnp\n"
        "def grad(w, idx, v):\n"
        "    return w.at[idx].add(v)\n"
    )
    report = run(tmp_path, {
        "twtml_tpu/ops/newop.py": scatter,
        "twtml_tpu/models/newmodel.py": scatter,
        "twtml_tpu/streaming/hostside.py": scatter,  # not step code
    })
    assert [(f.path, f.rule) for f in report.findings] == [
        ("twtml_tpu/models/newmodel.py", "TW004"),
        ("twtml_tpu/ops/newop.py", "TW004"),
    ]


def test_tw005_fires_on_silent_swallow_quiet_on_handled(tmp_path):
    report = run(tmp_path, {"twtml_tpu/streaming/sw.py": (
        "import logging\n"
        "def a():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        pass\n"
        "def b():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        logging.exception('batch failed')\n"
        "def c():\n"
        "    try:\n"
        "        work()\n"
        "    except ValueError:\n"
        "        pass\n"
        "def d():\n"
        "    try:\n"
        "        work()\n"
        "    except BaseException:\n"
        "        raise\n"
    )})
    assert [(f.rule, f.line) for f in report.findings] == [("TW005", 5)]


def test_tw005_try_parity_files_are_exempt(tmp_path):
    swallow = "try:\n    x = 1\nexcept Exception:\n    pass\n"
    report = run(tmp_path, {
        "twtml_tpu/telemetry/session_stats.py": swallow,
        "twtml_tpu/telemetry/web_client.py": swallow,
    })
    assert report.findings == []


def test_tw006_fires_on_wall_clock_in_replay_scope(tmp_path):
    report = run(tmp_path, {"twtml_tpu/serving/sched.py": (
        "import time\n"
        "def tick():\n"
        "    t = time.time()\n"
        "    d = time.monotonic()\n"
        "    return t, d\n"
    )})
    assert [(f.rule, f.line) for f in report.findings] == [("TW006", 3)]


def test_tw006_out_of_scope_files_unflagged(tmp_path):
    report = run(tmp_path, {"twtml_tpu/telemetry/clocky.py": (
        "import time\nNOW = []\n"
        "def sample():\n"
        "    NOW.append(time.time())\n"
    )})
    assert report.findings == []


def test_tw007_both_directions(tmp_path):
    report = run(tmp_path, {
        "twtml_tpu/config.py": (
            "class ConfArguments:\n"
            "    def parse(self, args):\n"
            "        flag = args[0]\n"
            "        if flag == '--foo':\n"
            "            pass\n"
            "        elif flag == '--undocumented':\n"
            "            pass\n"
            "        return self\n"
        ),
        "README.md": "Use `--foo` and the imaginary `--ghostFlag`.\n",
    })
    msgs = {f.rule: f for f in report.findings}
    assert set(msgs) == {"TW007"}
    texts = [f.message for f in report.findings]
    assert any("--undocumented" in t and "documented in neither" in t
               for t in texts)
    assert any("--ghostFlag" in t and "exists in no parser" in t
               for t in texts)
    # --ghostFlag anchors to the doc that mentions it
    assert any(f.path == "README.md" for f in report.findings)


# ---------------------------------------------------------------------------
# suppression semantics


_VIOLATION = (
    "import jax\n"
    "def f(out):\n"
    "    return jax.device_get(out){}\n"
)


def test_suppression_with_reason_silences(tmp_path):
    report = run(tmp_path, {"twtml_tpu/streaming/v.py": _VIOLATION.format(
        "  # lawcheck" ": disable=TW002 -- seeded test exemption"
    )})
    assert report.findings == [] and len(report.suppressed) == 1
    assert report.exit_code == 0


def test_suppression_without_reason_is_malformed(tmp_path):
    report = run(tmp_path, {"twtml_tpu/streaming/v.py": _VIOLATION.format(
        "  # lawcheck" ": disable=TW002"
    )})
    assert report.exit_code == 2
    assert any("without a reason" in m.message for m in report.malformed)


def test_suppression_unknown_rule_is_malformed(tmp_path):
    report = run(tmp_path, {"twtml_tpu/streaming/v.py": _VIOLATION.format(
        "  # lawcheck" ": disable=TW999 -- no such law"
    )})
    assert report.exit_code == 2
    assert any("unknown rule" in m.message for m in report.malformed)


def test_suppression_only_covers_its_own_line(tmp_path):
    report = run(tmp_path, {"twtml_tpu/streaming/v.py": (
        "import jax\n"
        "# lawcheck" ": disable=TW002 -- wrong line, must not apply below\n"
        "def f(out):\n"
        "    return jax.device_get(out)\n"
    )})
    assert [f.rule for f in report.findings] == ["TW002"]


def test_wrong_rule_suppression_does_not_silence(tmp_path):
    report = run(tmp_path, {"twtml_tpu/streaming/v.py": _VIOLATION.format(
        "  # lawcheck" ": disable=TW004 -- names the wrong law"
    )})
    assert [f.rule for f in report.findings] == ["TW002"]


# ---------------------------------------------------------------------------
# baseline semantics


def test_baseline_grandfathers_by_fingerprint(tmp_path):
    root = mini_repo(tmp_path, {
        "twtml_tpu/streaming/v.py": _VIOLATION.format(""),
    })
    bl = root / "baseline.json"
    bl.write_text(json.dumps(
        {"findings": ["TW002:twtml_tpu/streaming/v.py:3"]}
    ))
    report = engine.run_repo(root=str(root), baseline_path=str(bl))
    assert report.findings == [] and len(report.baselined) == 1
    assert report.exit_code == 0


def test_stale_baseline_entry_is_reported(tmp_path):
    root = mini_repo(tmp_path, {})
    bl = root / "baseline.json"
    bl.write_text(json.dumps({"findings": ["TW002:gone.py:1"]}))
    report = engine.run_repo(root=str(root), baseline_path=str(bl))
    assert report.stale_baseline == ["TW002:gone.py:1"]
    assert report.exit_code == 0  # stale entries don't fail, they nag


def test_corrupt_baseline_is_malformed(tmp_path):
    root = mini_repo(tmp_path, {})
    bl = root / "baseline.json"
    bl.write_text("{not json")
    report = engine.run_repo(root=str(root), baseline_path=str(bl))
    assert report.exit_code == 2


def test_unparsable_target_file_is_malformed(tmp_path):
    report = run(tmp_path, {"twtml_tpu/broken.py": "def f(:\n"})
    assert report.exit_code == 2
    assert any("cannot parse" in m.message for m in report.malformed)


# ---------------------------------------------------------------------------
# CLI contract: --json shape and exit codes


def _main(tmp_path, files, *extra):
    root = mini_repo(tmp_path, files)
    return engine.main([
        "--root", str(root), "--baseline", str(root / "baseline.json"),
        *extra,
    ])


def test_cli_exit_codes(tmp_path, capsys):
    assert _main(tmp_path / "clean", {}) == 0
    assert _main(tmp_path / "dirty", {
        "twtml_tpu/streaming/v.py": _VIOLATION.format(""),
    }) == 1
    assert _main(tmp_path / "malformed", {
        "twtml_tpu/broken.py": "def f(:\n",
    }) == 2
    capsys.readouterr()


def test_cli_json_mode(tmp_path, capsys):
    code = _main(tmp_path, {
        "twtml_tpu/streaming/v.py": _VIOLATION.format(""),
    }, "--json")
    out = json.loads(capsys.readouterr().out)
    assert code == 1 and out["exit_code"] == 1
    (finding,) = out["findings"]
    assert finding["rule"] == "TW002"
    assert finding["path"] == "twtml_tpu/streaming/v.py"
    assert finding["line"] == 3
    assert "FetchPipeline" in finding["message"]  # cites the seam law


def test_cli_list_rules_names_all_seven(tmp_path, capsys):
    assert engine.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in sorted(rule_ids()):
        assert rid in out


def test_write_baseline_roundtrip(tmp_path, capsys):
    files = {"twtml_tpu/streaming/v.py": _VIOLATION.format("")}
    assert _main(tmp_path, files, "--write-baseline") == 0
    capsys.readouterr()
    # the grandfathered finding no longer fails the gate
    assert _main(tmp_path, files) == 0


# ---------------------------------------------------------------------------
# registry + acceptance


def test_tw008_fires_on_fresh_pack_alloc(tmp_path):
    """r17 arena law: a pack-path function allocating its wire buffer
    fresh — np.empty, or np.concatenate without an out= destination —
    fires; the blessed arena-lease pattern right next to it stays
    quiet."""
    report = run(tmp_path, {"twtml_tpu/features/batch.py": (
        "import numpy as np\n"
        "from .arena import lease_wire\n"
        "def pack_batch(batch):\n"
        "    buf = np.empty((1024,), np.uint8)\n"        # fires
        "    return np.concatenate([buf, buf])\n"        # fires (no out=)
        "def pack_ragged_sharded(rb):\n"
        "    lease = lease_wire(2048)\n"
        "    out = lease.buf\n"
        "    np.concatenate([out[:1024], out[1024:]], out=out)\n"  # quiet
        "    return out\n"
        "def featurize_helper():\n"
        "    return np.zeros((64,), np.uint8)\n"          # out of scope
    )})
    lines = [f.line for f in report.findings if f.rule == "TW008"]
    assert lines == [4, 5]


def test_tw008_scoped_to_pack_hot_path(tmp_path):
    """The same allocations OUTSIDE the scoped modules (or outside
    pack-path functions) are not findings — the law covers the wire
    buffer the transport client retains, not every numpy call."""
    report = run(tmp_path, {"twtml_tpu/streaming/sources.py": (
        "import numpy as np\n"
        "def pack_batch(batch):\n"
        "    return np.empty((1024,), np.uint8)\n"
    )})
    assert "TW008" not in rules_fired(report)


def test_tw010_fires_on_historian_sampling_outside_the_seam(tmp_path):
    """ISSUE 20 law: historian.sample() may run ONLY from the SessionStats
    publish seam — a second sampling site pays new snapshot work on a hot
    path (or invites a device fetch the counted-fetch law forbids)."""
    report = run(tmp_path, {"twtml_tpu/streaming/context.py": (
        "from twtml_tpu.telemetry import historian as _historian\n"
        "def _lockstep_loop(self):\n"
        "    _historian.sample()\n"                      # fires
        "    _historian.get().sample()\n"                # fires too
    )})
    lines = [f.line for f in report.findings if f.rule == "TW010"]
    assert lines == [3, 4]


def test_tw010_quiet_in_the_seam_and_on_other_samples(tmp_path):
    report = run(tmp_path, {
        "twtml_tpu/telemetry/session_stats.py": (
            "from . import historian as _historian\n"
            "def publish_metrics(self):\n"
            "    _historian.sample()\n"                  # THE seam
        ),
        "twtml_tpu/streaming/sources.py": (
            "import random\n"
            "def pick(xs):\n"
            "    return random.sample(xs, 3)\n"          # not historian
        ),
    })
    assert "TW010" not in rules_fired(report)


def test_rule_registry_is_stable():
    rules = all_rules()
    ids = [r.id for r in rules]
    assert len(ids) == len(set(ids)) and len(ids) >= 7
    for r in rules:
        assert r.title and r.law, f"{r.id} must cite its law"


@pytest.mark.parametrize("inside", ["tmpdir", "home", "_scratch/t",
                                    "_checkout/parent"])
def test_a_tmpdir_or_home_inside_the_checkout_is_not_the_repo(
        tmp_path, monkeypatch, inside):
    """The driver gives each checkout a TMPDIR and a HOME of its own; where
    they lie inside it, pytest's ``tmp_path`` trees (this file's seeded
    violations among them) and a tree unpacked for a chip pair are not the
    repo's code. Anywhere else the same file IS judged."""
    import tempfile

    bad = {"twtml_tpu/broken.py": "def (:\n"}
    assert run(tmp_path, bad).exit_code == 2       # in the repo: judged
    (tmp_path / "twtml_tpu/broken.py").unlink()
    mini_repo(tmp_path / inside, bad)
    if inside == "tmpdir":
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path / inside))
    elif inside == "home":
        monkeypatch.setenv("HOME", str(tmp_path / inside))
    else:
        assert inside.split("/")[0] in engine._SKIP_DIRS
    report = engine.run_repo(root=str(tmp_path),
                             baseline_path=str(tmp_path / "baseline.json"))
    assert report.exit_code == 0 and report.malformed == []
    if inside in ("tmpdir", "home"):
        monkeypatch.undo()                         # placed elsewhere: judged
        assert engine.run_repo(
            root=str(tmp_path),
            baseline_path=str(tmp_path / "baseline.json")).exit_code == 2


def test_repo_is_clean_with_empty_baseline():
    """THE acceptance criterion: the real checkout passes every law with
    nothing grandfathered — every remaining deviation is an inline
    suppression carrying its written reason."""
    report = engine.run_repo()
    assert [m.render() for m in report.malformed] == []
    assert [f.render() for f in report.findings] == []
    with open(engine._DEFAULT_BASELINE, encoding="utf-8") as fh:
        assert json.load(fh)["findings"] == []
    assert report.stale_baseline == []
    assert report.exit_code == 0
