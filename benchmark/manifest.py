"""``BENCHMARK.json`` and the files it names: loading, finding files by
name, and the lint both ``python -m benchmark.lint`` and ``run.py`` apply.

Everything that belongs to one configuration, one traffic mix, one work
count or one per-layer metric sits in a file of its own that is FOUND BY
NAME here; adding a cell needs no edit to any module (benchmark/README.md).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# what a configuration's `reduced` may never name (the contract's widths)
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_size|"
    r"head_dim|expansion|experts_per_tok|numTextFeatures|numNumberFeatures)",
    re.IGNORECASE,
)
MAX_RUN_SECONDS = 51
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def load(path: str = MANIFEST) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def traffic_path(mix: str) -> "str | None":
    for ext in TRAFFIC_EXT:
        p = os.path.join(HERE, "traffic", mix + ext)
        if os.path.exists(p):
            return p
    return None


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(path: str):
    """Import a file under the benchmark by path. A file whose name is not
    a Python identifier (``device_idle_share.train``, ``linear1004-dp4``)
    is loaded as a module of its directory's package all the same."""
    parts = os.path.relpath(path, ROOT)[:-len(".py")].split(os.sep)
    if all(p.isidentifier() for p in parts):
        return importlib.import_module(".".join(parts))
    name = ".".join(parts[:-1] + [re.sub(r"\W", "_", parts[-1])])
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metric_path(metric: str) -> str:
    return os.path.join(HERE, "layer_metrics", metric + ".py")


def work_count_path(config: dict) -> str:
    return os.path.join(
        HERE, "work_counts", config.get("work_count", config["name"]) + ".py"
    )


def driver_path(kind: str) -> str:
    return os.path.join(HERE, "drivers", kind + ".py")


def cell(manifest: dict, workload: str) -> dict:
    """Everything one run needs, resolved by name: the workload entry, its
    configuration (entry + file), its traffic mix (file) and the metrics it
    reports."""
    wl = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json (has: "
            f"{', '.join(w['name'] for w in manifest['workloads'])})"
        )
    entry = next(c for c in manifest["configs"] if c["name"] == wl["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(traffic_path(wl["traffic"]))
    if "generator" not in traffic:
        # a mix may take another mix's generator parameters by name
        traffic["generator"] = load_json(
            traffic_path(traffic["generator_of"]))["generator"]
    return {
        "workload": wl,
        "config_entry": entry,
        "config": config,
        "traffic": traffic,
        "traffic_path": traffic_path(wl["traffic"]),
        "end_to_end": [m for m in manifest["end_to_end"] if _reports(m, wl)],
        "per_layer": [m for m in manifest["per_layer"] if _reports(m, wl)],
    }


def _reports(metric: dict, wl: dict) -> bool:
    return "workloads" not in metric or wl["name"] in metric["workloads"]


# --------------------------------------------------------------------------
# the lint


def _one_line(s, lo=1, hi=200) -> bool:
    return (isinstance(s, str) and lo <= len(s) <= hi
            and "\n" not in s and "\r" not in s and "\t" not in s)


def lint(manifest_path: str = MANIFEST) -> list:
    """Every fault found, as strings; empty when the manifest and the files
    it names meet the contract's rules."""
    out: list = []
    bad = out.append
    try:
        raw = open(manifest_path, "rb").read()
    except OSError as exc:
        return [f"cannot read {manifest_path}: {exc}"]
    if len(raw) > 64 * 1024:
        bad(f"BENCHMARK.json is {len(raw)} bytes; at most 65536")
    try:
        m = json.loads(raw)
    except ValueError as exc:
        return [f"BENCHMARK.json is not JSON: {exc}"]
    if not isinstance(m, dict) or set(m) != KEYS["top"]:
        return [f"top-level keys must be exactly {sorted(KEYS['top'])}, "
                f"found {sorted(m) if isinstance(m, dict) else type(m)}"]

    # -- command, paths, run_seconds
    cmd, paths = m["command"], m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p) for p in paths)):
        bad("paths: 1 to 16 relative paths of letters, digits, _ . - /")
        paths = []
    for p in paths:
        if p.startswith("/") or ".." in p.split("/"):
            bad(f"paths: {p!r} leaves the repo")
        elif not os.path.isdir(os.path.join(ROOT, p)):
            bad(f"paths: {p!r} is not a directory")
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_one_line(w) for w in cmd)):
        bad("command: a list of 1 to 32 one-line strings of 1 to 200 characters")
    else:
        for w in cmd:
            if w.startswith("/") or ".." in w.split("/"):
                bad(f"command: {w!r} is an absolute path or leads out of the repo")
            elif os.path.exists(os.path.join(ROOT, w)) and not any(
                w == p or w.startswith(p.rstrip("/") + "/") for p in paths
            ):
                bad(f"command: {w!r} names a file of the repo outside paths")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= MAX_RUN_SECONDS):
        bad(f"run_seconds: a whole number from 1 to {MAX_RUN_SECONDS}, not {rs!r}")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    def entries(key, kind, lo, hi):
        items = m[key]
        if not (isinstance(items, list) and lo <= len(items) <= hi
                and all(isinstance(e, dict) for e in items)):
            bad(f"{key}: a list of {lo} to {hi} objects")
            return []
        names = [e.get("name") for e in items]
        for n in names:
            if not (isinstance(n, str) and NAME.match(n)):
                bad(f"{key}: name {n!r} must be 1 to 64 characters from letters, "
                    "digits, '_', '.' and '-', starting with a letter, digit or '_'")
        for n in {n for n in names if names.count(n) > 1}:
            bad(f"{key}: name {n!r} appears twice")
        for e in items:
            extra = set(e) - KEYS[kind] - ({"workloads"} if kind in (
                "end_to_end", "per_layer") else set())
            missing = KEYS[kind] - set(e)
            if extra or missing:
                bad(f"{key} {e.get('name')!r}: keys must be {sorted(KEYS[kind])}"
                    f" (extra {sorted(extra)}, missing {sorted(missing)})")
        return [e for e in items if not (KEYS[kind] - set(e))]

    # -- configs
    configs = entries("configs", "config", 1, 24)
    files = []
    for c in configs:
        n = c["name"]
        if not _one_line(c["source"]):
            bad(f"config {n!r}: source must be one line of 1 to 200 characters "
                f"(has {len(str(c['source']))})")
        if not _one_line(c["why"]):
            bad(f"config {n!r}: why must be one line of 1 to 200 characters")
        f = c["file"]
        if not (isinstance(f, str) and PATH.match(f) and under_paths(f)):
            bad(f"config {n!r}: file {f!r} must lie under paths")
        elif not os.path.isfile(os.path.join(ROOT, f)):
            bad(f"config {n!r}: file {f!r} does not exist")
        else:
            try:
                body = load_json(os.path.join(ROOT, f))
                if not isinstance(body, dict):
                    raise ValueError("not an object")
            except ValueError as exc:
                bad(f"config {n!r}: file {f!r} is not a JSON object: {exc}")
                body = {}
            wc = work_count_path({"name": n, **body})
            if not os.path.isfile(wc):
                bad(f"config {n!r}: no work count at {os.path.relpath(wc, ROOT)}")
            # the learner is named in the file: both names have to lead
            # somewhere (file checks: the lint imports neither)
            app = body.get("app")
            if app is not None and not (
                isinstance(app, str) and app.isidentifier() and os.path.isfile(
                    os.path.join(ROOT, "twtml_tpu", "apps", app + ".py"))
            ):
                bad(f"config {n!r}: app {app!r} names no twtml_tpu/apps/<app>.py")
            ref = body.get("reference")
            if ref is not None and not (
                isinstance(ref, str) and PATH.match(ref) and under_paths(ref)
                and ref.endswith(".py")
                and os.path.isfile(os.path.join(ROOT, ref))
            ):
                bad(f"config {n!r}: reference {ref!r} names no .py file "
                    "under paths")
        files.append(f)
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16
                and all(isinstance(k, str) and NAME.match(k) for k in red)):
            bad(f"config {n!r}: reduced must be at most 16 names")
        else:
            for k in red:
                if WIDTH.search(k):
                    bad(f"config {n!r}: reduced names a width, {k!r}")
    for f in {f for f in files if files.count(f) > 1}:
        bad(f"configs: file {f!r} belongs to two configurations")

    # -- workloads
    workloads = entries("workloads", "workload", 1, 24)
    config_names = {c["name"] for c in configs}
    pairs = []
    for w in workloads:
        n = w["name"]
        if w["config"] not in config_names:
            bad(f"workload {n!r}: config {w['config']!r} is not in configs")
        if not (isinstance(w["traffic"], str) and NAME.match(w["traffic"])):
            bad(f"workload {n!r}: traffic {w['traffic']!r} is not a name")
        elif traffic_path(w["traffic"]) is None:
            bad(f"workload {n!r}: no traffic file benchmark/traffic/"
                f"{w['traffic']}.(json|jsonl|toml|txt|csv)")
        else:
            try:
                mix = load_json(traffic_path(w["traffic"]))
            except ValueError as exc:
                bad(f"workload {n!r}: traffic file is not JSON: {exc}")
                mix = {}
            kind = mix.get("kind")
            if (mix.get("generator") or {}).get("runs"):
                from . import gen   # NumPy: only a mix with runs pays for it

                for fault in gen.lint_runs(mix["generator"]):
                    bad(f"workload {n!r}: {fault}")
            if kind is not None and not (
                isinstance(kind, str) and NAME.match(kind)
                and os.path.isfile(driver_path(kind))
            ):
                bad(f"workload {n!r}: traffic kind {kind!r} has no driver "
                    f"benchmark/drivers/{kind}.py")
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            bad(f"workload {n!r}: chips must be 1 or 4")
        if not _one_line(w["why"]):
            bad(f"workload {n!r}: why must be one line of 1 to 200 characters "
                f"(has {len(str(w['why']))})")
        pairs.append((w["config"], w["traffic"]))
    for p in {p for p in pairs if pairs.count(p) > 1}:
        bad(f"workloads: the pair (config, traffic) {p} appears twice")
    for c in config_names - {w["config"] for w in workloads}:
        bad(f"config {c!r} is used by no workload")
    four = sum(1 for w in workloads if w["chips"] == 4)
    if four > max(1, len(workloads) // 4):
        bad(f"{four} of {len(workloads)} workloads ask for 4 chips; at most "
            f"{max(1, len(workloads) // 4)} (25%, rounded down, and one always)")

    # -- metrics
    wl_names = {w["name"] for w in workloads}
    e2e = entries("end_to_end", "end_to_end", 1, 16)
    layer = entries("per_layer", "per_layer", 1, 128)
    both = [x["name"] for x in e2e + layer]
    for n in {n for n in both if both.count(n) > 1}:
        bad(f"metric name {n!r} is used twice")

    def cells_of(metric) -> set:
        return set(metric["workloads"]) if "workloads" in metric else set(wl_names)

    for x in e2e + layer:
        n = x["name"]
        if not (isinstance(x["unit"], str) and UNIT.match(x["unit"])):
            bad(f"metric {n!r}: unit {x['unit']!r} must be 1 to 16 characters "
                "from letters, digits, '_', '/', '%', '.' and '-'")
        if x["better"] not in ("lower", "higher"):
            bad(f"metric {n!r}: better must be 'lower' or 'higher'")
        if x["source"] not in SOURCES:
            bad(f"metric {n!r}: source must be one of {SOURCES}")
        if "workloads" in x:
            wls = x["workloads"]
            if not (isinstance(wls, list) and wls
                    and all(isinstance(k, str) for k in wls)):
                bad(f"metric {n!r}: workloads must be a non-empty list of names")
                x["workloads"] = []
            for k in set(x["workloads"]) - wl_names:
                bad(f"metric {n!r}: workloads names {k!r}, which is not a workload")
    for x in e2e:
        n = x["name"]
        if x["source"] not in ("host_clock", "device_trace"):
            bad(f"end_to_end {n!r}: source must be host_clock or device_trace")
        b = x["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= 0.1):
            bad(f"end_to_end {n!r}: bound {b!r} must lie in [0.01, 0.1]")
    setup = [x for x in e2e if x["name"] == "setup_s"]
    if not setup:
        bad("end_to_end: one metric must be setup_s")
    elif "workloads" in setup[0]:
        bad("end_to_end setup_s: every cell reports it; it takes no workloads key")
    e2e_by_name = {x["name"]: x for x in e2e}
    for x in layer:
        n = x["name"]
        if not (isinstance(x["layer"], str) and NAME.match(x["layer"])):
            bad(f"per_layer metric {n}: layer must be 1 to 64 characters from "
                "letters, digits, '_', '.' and '-', starting with a letter, "
                f"digit or '_', not {x['layer']!r}")
        if x["unit"] == "%" and n.endswith("roofline_share"):
            bad(f"per_layer {n!r}: name a roofline share <kernel>_roofline")
        target = e2e_by_name.get(x["moves"])
        if target is None:
            bad(f"per_layer {n!r}: moves {x['moves']!r} is not an end_to_end metric")
            continue
        for k in sorted(cells_of(x) - cells_of(target)):
            bad(f"per_layer {n!r} is reported in {k!r}, which does not report "
                f"the metric it moves, {x['moves']!r}")
        if not os.path.isfile(layer_metric_path(n)):
            bad(f"per_layer {n!r}: no reader at benchmark/layer_metrics/{n}.py")
    for w in workloads:
        mine = [x for x in e2e if w["name"] in cells_of(x)]
        if not any(x["name"] != "setup_s" for x in mine):
            bad(f"workload {w['name']!r} reports no end_to_end metric but setup_s")
        if not any(w["name"] in cells_of(x) for x in layer):
            bad(f"workload {w['name']!r} reports no per_layer metric")

    # -- files under paths are named from the characters of a name and '/'
    for p in paths:
        for base, dirs, names in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in names:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                if not PATH.match(rel):
                    bad(f"file name {rel!r} uses characters outside "
                        "letters, digits, '_', '.', '-' and '/'")
    return out
