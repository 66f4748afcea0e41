"""Sanitized differential harness for the C parity fast paths.

Builds ``native/*.cpp`` with ASan/UBSan instrumentation (honoring the
``TWTML_NATIVE_SANITIZE`` seam in features/native.py) into a TEMP library
— never clobbering the production ``.so`` — and drives the same
differentials the parity law rests on, jax-free:

- ``hash_texts`` vs the pure-Python ground truth (features/hashing.py:
  char_bigrams + hashing_tf_counts), on an adversarial corpus (emoji,
  lone surrogates, empties, 1-unit rows, long rows, seeded fuzz);
- ``parse_tweet_block`` vs ``parse_tweet_block_wire`` byte-parity on
  crafted JSONL blocks (unicode, garbage lines, truncated tails, the
  retweet-count filter window);
- ``pad_units`` (narrow + wide + ASCII fold) vs a numpy reference.

Memory errors (OOB reads on ragged offsets, the classic parser bug class)
abort with a sanitizer report; semantic divergence exits 1. Exit 0 = the
instrumented library is parity-clean; exit 2 = environment cannot run the
harness (no g++ / no sanitizer runtime) — callers decide whether that is
fatal (CI: yes; the slow-marked test skips).

ASan's runtime must be loaded before CPython itself, so when ``asan`` is
requested the script re-execs itself once with ``LD_PRELOAD`` pointing at
g++'s libasan (leak checking off: CPython "leaks" by design).

Usage::

    python tools/native_sanity.py                 # ubsan+asan (default)
    TWTML_NATIVE_SANITIZE=ubsan python tools/native_sanity.py
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import tempfile
import types

_REEXEC_MARK = "TWTML_NATIVE_SANITY_REEXEC"


def _fail_env(msg: str) -> "int":
    print(f"native_sanity: SKIP-ENV {msg}", file=sys.stderr)
    return 2


def _sanitizer_runtime(name: str) -> str | None:
    try:
        out = subprocess.run(
            ["g++", f"-print-file-name={name}"],
            capture_output=True, text=True, timeout=30, check=True,
        ).stdout.strip()
    except Exception:
        return None
    return out if os.path.sep in out and os.path.exists(out) else None


def _maybe_reexec(modes: set[str]) -> None:
    """Re-exec once with libasan preloaded when asan is requested (its
    interceptors must initialize before CPython's first allocation)."""
    if "asan" not in modes or os.environ.get(_REEXEC_MARK):
        return
    rt = _sanitizer_runtime("libasan.so")
    if rt is None:
        raise SystemExit(_fail_env("libasan.so not found via g++"))
    env = dict(os.environ)
    env[_REEXEC_MARK] = "1"
    env["LD_PRELOAD"] = " ".join(
        p for p in (rt, env.get("LD_PRELOAD", "")) if p
    )
    env.setdefault("ASAN_OPTIONS", "detect_leaks=0:abort_on_error=1")
    os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
              + sys.argv[1:], env)


def _stub_jax() -> None:
    """features/__init__ registers two pytree nodes at import; the harness
    never builds jax pytrees, and importing real jax under an ASan preload
    drowns the report in uninstrumented-jaxlib noise — stub the one entry
    point the import chain touches. A real already-imported jax wins."""
    if "jax" in sys.modules:
        return
    fake = types.ModuleType("jax")
    fake.tree_util = types.SimpleNamespace(
        register_pytree_node=lambda *a, **k: None
    )
    sys.modules["jax"] = fake


# ---------------------------------------------------------------------------
# corpora


def _texts_corpus() -> list[str]:
    rng = random.Random(42)
    crafted = [
        "", "a", "aa", "plain ascii tweet about tpus",
        "MiXeD CaSe ASCII with    spaces",
        "héllo wörld",  # BMP latin-1 supplement
        "こんにちは",  # CJK
        "\U0001f600\U0001f680",  # astral emoji: surrogate-pair bigrams
        "a\U0001f600b",
        "\ud800",  # lone high surrogate (json.loads produces these)
        "x\udfffy",  # lone low surrogate mid-string
        "aa" * 2000,  # long row
        "\t\n weird\x00控制 chars\x1f",
    ]
    alphabet = "abcdefghij éöあ\U0001f600"
    fuzz = [
        "".join(rng.choice(alphabet) for _ in range(rng.randrange(0, 80)))
        for _ in range(200)
    ]
    return crafted + fuzz


def _block_corpus() -> bytes:
    rng = random.Random(7)

    def rt(text, count=500, **extra):
        inner = {"text": text, "retweet_count": count,
                 "user": {"followers_count": rng.randrange(0, 10**6),
                          "favourites_count": rng.randrange(0, 10**5),
                          "friends_count": rng.randrange(0, 10**4)},
                 "timestamp_ms": "1785313333333"}
        inner.update(extra)
        return {"text": "RT", "retweeted_status": inner}

    lines: list[str] = []
    for i in range(64):
        lines.append(json.dumps(rt(f"plain ascii tweet {i}", count=100 + i)))
    lines.append(json.dumps(rt("héllo été", count=150),
                            ensure_ascii=False))
    lines.append(json.dumps(rt("\U0001f600 emoji \U0001f680", count=151)))
    lines.append(json.dumps(rt("edge counts", count=0)))
    lines.append(json.dumps(rt("over the window", count=10**7)))
    lines.append(json.dumps({"text": "no retweet here"}))  # filtered
    lines.append("{garbage not json")  # bad line
    lines.append("")  # blank
    lines.append(json.dumps(rt("escaped \\\" quote \\u00e9", count=152)))
    lines.append(json.dumps(rt("x" * 5000, count=153)))  # over kMaxTextUnits
    return ("\n".join(lines) + "\n").encode("utf-8")


# ---------------------------------------------------------------------------
# differentials


def _check_hash_parity(native, hashing, np) -> list[str]:
    errors: list[str] = []
    texts = [t.lower() for t in _texts_corpus()]
    num_features = 2**18
    encoded = native.encode_texts(texts)
    lengths = np.diff(encoded[1])
    l_max = max(64, int(lengths.max()))
    idx = np.zeros((len(texts), l_max), dtype=np.int32)
    val = np.zeros((len(texts), l_max), dtype=np.float32)
    ntok = native.hash_texts(texts, num_features, idx, val, encoded=encoded)
    if ntok is None:
        return ["hash_texts returned None (fallback) on the corpus"]
    for i, text in enumerate(texts):
        want = hashing.hashing_tf_counts(
            hashing.char_bigrams(text), num_features
        )
        got: dict[int, float] = {}
        for j in range(l_max):
            if val[i, j] != 0:
                got[int(idx[i, j])] = got.get(int(idx[i, j]), 0.0) + float(
                    val[i, j]
                )
        if got != want:
            errors.append(
                f"hash row {i} diverged from features/hashing.py "
                f"(text={text[:40]!r}...)"
            )
    return errors


def _check_pad_units(native, np) -> list[str]:
    errors: list[str] = []
    texts = [t.lower() for t in _texts_corpus()[:40]]
    encoded = native.encode_texts(texts)
    units, offsets = encoded
    lengths = np.diff(offsets)
    l_max = max(8, int(lengths.max()))
    for narrow in (False, True):
        if narrow and any(u > 0xFF for u in units.tolist()):
            ascii_texts = [t for t in texts if t.isascii()]
            enc = native.encode_texts(ascii_texts)
        else:
            ascii_texts, enc = texts, encoded
        u, off = enc
        n = len(ascii_texts)
        got = native.pad_units(enc, n, n + 3, l_max, ascii_lower=False,
                               narrow=narrow)
        if got is None:
            errors.append(f"pad_units(narrow={narrow}) returned None")
            continue
        buf, length = got
        want_dtype = np.uint8 if narrow else np.uint16
        if buf.dtype != want_dtype:
            errors.append(f"pad_units(narrow={narrow}) dtype {buf.dtype}")
        for i in range(n):
            row = u[off[i]:off[i + 1]]
            if int(length[i]) != len(row) or not (
                buf[i, :len(row)].astype(np.uint16) == row.astype(np.uint16)
            ).all() or buf[i, len(row):].any():
                errors.append(f"pad_units(narrow={narrow}) row {i} mismatch")
                break
        if buf[n:].any() or length[n:].any():
            errors.append(f"pad_units(narrow={narrow}) padding rows dirty")
    return errors


def _check_block_wire_parity(native, np) -> list[str]:
    errors: list[str] = []
    data = _block_corpus()
    for begin, end in ((0, 2**62), (120, 160), (0, 1)):
        legacy = native.parse_tweet_block(data, begin, end)
        wire = native.parse_tweet_block_wire(data, begin, end)
        if legacy is None or wire is None:
            errors.append(f"parser unavailable (begin={begin})")
            continue
        l_num, l_units, l_off, l_ascii, l_cons, l_bad = legacy
        w_num, w_units, w_off, w_ascii, w_cons, w_bad = wire
        tag = f"[{begin},{end})"
        if not (np.array_equal(l_num, w_num)
                and np.array_equal(l_off, w_off)
                and np.array_equal(l_ascii, w_ascii)
                and l_cons == w_cons):
            errors.append(f"block {tag}: legacy/wire metadata diverged")
            continue
        if not np.array_equal(
            l_units.astype(np.uint16), w_units.astype(np.uint16)
        ):
            errors.append(f"block {tag}: unit payloads diverged")
        if len(w_ascii) and w_ascii.all() and w_units.dtype != np.uint8:
            errors.append(f"block {tag}: all-ASCII block not narrow")
        # bad-line counts: the wire parser's keyless-line prescreen may
        # UNDERCOUNT JSON-shaped lines with no "retweeted_status" key —
        # the documented telemetry-only divergence (PARITY.md, the
        # zero-copy wire emitter); kept-row payloads above are exact
        # either way
        if w_bad > l_bad:
            errors.append(f"block {tag}: wire bad-count exceeds legacy "
                          f"({w_bad} > {l_bad})")
        # truncated tail: both parsers must stop at the same consumed byte
        cut = data[: len(data) - 37]
        lt = native.parse_tweet_block(cut, begin, end)
        wt = native.parse_tweet_block_wire(cut, begin, end)
        if lt[4] != wt[4] or wt[5] > lt[5]:
            errors.append(f"block {tag}: truncated-tail consumed/bad differ")
    return errors


def _check_codec_parity(native, np) -> "list[str]":
    """C ``digram_encode`` vs the pure-numpy ground truth
    (features/wirecodec.encode_np), byte-for-byte, plus a decode
    round-trip — the compressed-wire parity law (r15) under ASan/UBSan
    (the greedy loop reads pairs at the buffer tail: the OOB class)."""
    from twtml_tpu.features import wirecodec as wc

    errors: list[str] = []
    rng = random.Random(99)
    bufs = [
        np.zeros((0,), np.uint8),
        np.zeros((1,), np.uint8),
        np.zeros((4096,), np.uint8),
        np.frombuffer(
            b"the quick brown fox https://t.co/Ab12 jumps over the lazy "
            b"dog again and again ", np.uint8,
        ),
    ]
    for _ in range(200):
        n = rng.randrange(0, 3000)
        bufs.append(np.frombuffer(
            bytes(rng.randrange(0, 128) for _ in range(n)), np.uint8
        ).copy())
    lut = wc.pair_lut()
    for i, buf in enumerate(bufs):
        ref = wc.encode_np(buf)
        got = native.digram_encode(buf, lut) if buf.shape[0] >= 2 else ref
        if got is None:
            return [f"codec[{i}]: digram_encode unavailable in the "
                    "instrumented library"]
        if not np.array_equal(got, ref):
            errors.append(f"codec[{i}]: C encode diverges from numpy "
                          f"ground truth (n={buf.shape[0]})")
            continue
        if not np.array_equal(wc.decode_np(ref, buf.shape[0]), buf):
            errors.append(f"codec[{i}]: decode round-trip mismatch")
    return errors


def _check_assemble_parity(native, np) -> "list[str]":
    """Fused wire assembler (native/wireassemble.cpp) vs the numpy pack
    pipeline (features/batch.py, the ground truth), byte-for-byte across
    flat / per-shard / coalesced-group layouts × codec on/off × narrow
    and int32 offsets × uint16-widened and incompressible fallbacks —
    under ASan/UBSan (segment-stride memcpys over ragged offsets: the
    OOB class the sanitizers exist for)."""
    from twtml_tpu.features import assemble
    from twtml_tpu.features.batch import (
        RaggedUnitBatch, align_ragged_shards, pack_batch,
        pack_ragged_group, pack_ragged_sharded, ragged_wire_arrays,
    )

    if not native.assemble_available():
        return ["wire_assemble unavailable in the instrumented library"]
    errors: list[str] = []
    rng = random.Random(17)

    def make(b, seed, wide=False, incompressible=False, row_len=96):
        r = random.Random(seed)
        rows = []
        for i in range(b - 3):
            n = r.randrange(1, row_len)
            if incompressible:
                rows.append([r.randrange(0, 128) for _ in range(n)])
            else:
                text = b"the streaming fox https://t.co/ab again "
                rows.append([text[j % len(text)] for j in range(n)])
        if wide and rows:
            rows[0] = rows[0] + [0x3042]
        units = np.array(
            [u for row in rows for u in row], np.uint16
        ).reshape(-1)
        offsets = np.zeros(len(rows) + 1, np.int64)
        np.cumsum([len(row) for row in rows], out=offsets[1:])
        flat, offs = ragged_wire_arrays(
            units, offsets, len(rows), b, narrow=not wide
        )
        numeric = np.arange(b * 4, dtype=np.float32).reshape(b, 4) + seed
        label = np.arange(b, dtype=np.float32) * 0.5
        mask = np.zeros(b, np.float32)
        mask[: len(rows)] = 1.0
        return RaggedUnitBatch(
            flat, offs, numeric, label, mask, row_len=row_len
        )

    def both(tag, fn):
        with assemble.forced("off"):
            ref = fn()
        with assemble.forced("on"):
            got = fn()
        if got.layout != ref.layout:
            errors.append(f"assemble {tag}: layout diverged")
        elif not np.array_equal(
            np.asarray(got.buffer), np.asarray(ref.buffer)
        ):
            errors.append(f"assemble {tag}: buffer bytes diverged")

    for codec in (None, "dict"):
        for wide in (False, True):
            for inc in (False, True):
                rb = make(32, rng.randrange(1 << 20), wide, inc)
                both(f"flat c={codec} w={wide} i={inc}",
                     lambda rb=rb, c=codec: pack_batch(rb, codec=c))
                for s in (1, 2, 4):
                    al = align_ragged_shards(rb, s)
                    both(f"shard{s} c={codec} w={wide} i={inc}",
                         lambda al=al, c=codec: pack_ragged_sharded(
                             al, codec=c))
                al2 = align_ragged_shards(rb, 2)
                parts = [
                    RaggedUnitBatch(
                        al2.units.copy(), al2.offsets.copy(),
                        al2.numeric + j, al2.label + j, al2.mask.copy(),
                        row_len=al2.row_len, num_shards=al2.num_shards,
                    )
                    for j in range(3)
                ]
                both(f"group c={codec} w={wide} i={inc}",
                     lambda p=parts, c=codec: pack_ragged_group(p, codec=c))
    rb = make(32, 5)
    both("flat raw-offs", lambda: pack_batch(rb, narrow_offsets=False))
    return errors


def _check_featurize_parity(native, np) -> "list[str]":
    """One-pass fused featurize (native/featurize.cpp) vs the
    Python/numpy ground truth (features/featurizer.py), bit-for-bit on
    both ingest paths — under ASan/UBSan (the narrowing units copy and
    the column-order indexed reads are exactly the OOB class the
    sanitizers exist for)."""
    from twtml_tpu.features import featurize_native as ffz
    from twtml_tpu.features.blocks import ParsedBlock
    from twtml_tpu.features.featurizer import Featurizer, Status

    if not native.featurize_available():
        return ["featurize_wire unavailable in the instrumented library"]
    errors: list[str] = []
    rng = random.Random(99)
    statuses = []
    for i, text in enumerate(_texts_corpus()):
        statuses.append(Status(
            text="RT", retweet_count=1,
            retweeted_status=Status(
                text=text,
                retweet_count=rng.choice((99, 100, 500, 1000, 1001)),
                followers_count=rng.randrange(0, 10**7),
                favourites_count=rng.randrange(0, 10**6),
                friends_count=rng.randrange(0, 10**5),
                created_at_ms=rng.randrange(0, 1785313333333),
            ),
        ))
        if i % 11 == 0:
            statuses.append(Status(text="plain, filtered out"))
    feat = Featurizer(now_ms=1785313333333)

    def both(tag, fn):
        with ffz.forced("off"):
            ref = fn()
        with ffz.forced("on"):
            got = fn()
        for f in ("units", "offsets", "numeric", "label", "mask"):
            a, b = getattr(ref, f), getattr(got, f)
            if a.dtype != b.dtype or not np.array_equal(a, b):
                errors.append(f"featurize {tag}: {f} diverged")
                return
        if ref.row_len != got.row_len:
            errors.append(f"featurize {tag}: row_len diverged")

    both("object mixed", lambda: feat.featurize_batch_ragged(
        statuses, row_bucket=0))
    ascii_only = [
        s for s in statuses
        if s.retweeted_status is not None
        and s.retweeted_status.text.isascii()
    ]
    both("object ascii", lambda: feat.featurize_batch_ragged(
        ascii_only, row_bucket=64, pre_filtered=True))
    both("object empty", lambda: feat.featurize_batch_ragged(
        [], row_bucket=8))
    parsed = native.parse_tweet_block_wire(_block_corpus(), 0, 10**9)
    if parsed is None:
        errors.append("featurize: block wire parser unavailable")
        return errors
    block = ParsedBlock(*parsed[:4])
    both("block mixed", lambda: feat.featurize_parsed_block(
        block, row_bucket=0, ragged=True))
    keep_ascii = [i for i in range(block.rows) if block.ascii[i]]
    if keep_ascii:
        stop = 0
        while stop < block.rows and block.ascii[stop]:
            stop += 1
        from twtml_tpu.features.blocks import slice_block

        ascii_blk = slice_block(block, 0, stop)
        both("block ascii prefix", lambda: feat.featurize_parsed_block(
            ascii_blk, row_bucket=32, ragged=True))
        wide_blk = ParsedBlock(
            ascii_blk.numeric, ascii_blk.units.astype(np.uint16),
            ascii_blk.offsets, ascii_blk.ascii,
        )
        both("block u16 ascii", lambda: feat.featurize_parsed_block(
            wide_blk, row_bucket=32, ragged=True))
    return errors


def main() -> int:
    os.environ.setdefault("TWTML_NATIVE_SANITIZE", "asan,ubsan")
    modes = {m.strip()
             for m in os.environ["TWTML_NATIVE_SANITIZE"].split(",") if m}
    _maybe_reexec(modes)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    _stub_jax()

    tmp = tempfile.mkdtemp(prefix="twtml-native-sanity-")
    os.environ.setdefault(
        "TWTML_NATIVE_LIB", os.path.join(tmp, "libfasthash_san.so")
    )
    import numpy as np

    from twtml_tpu.features import hashing, native

    if native.get_lib() is None:
        return _fail_env("instrumented library failed to build/load "
                         "(no g++, or sanitizer link failure)")
    errors: list[str] = []
    errors += _check_hash_parity(native, hashing, np)
    errors += _check_pad_units(native, np)
    errors += _check_block_wire_parity(native, np)
    errors += _check_codec_parity(native, np)
    errors += _check_assemble_parity(native, np)
    errors += _check_featurize_parity(native, np)
    for e in errors:
        print(f"native_sanity: FAIL {e}", file=sys.stderr)
    print(
        f"native_sanity: modes={','.join(sorted(modes)) or 'none'} "
        f"lib={os.environ['TWTML_NATIVE_LIB']} "
        f"{'FAIL ' + str(len(errors)) + ' differential(s)' if errors else 'PASS'}"
    )
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
