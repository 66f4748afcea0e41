"""Native (C++) featurizer parity: the ctypes fast path must produce exactly
the same hashed term-frequency sets as the pure-Python ground truth
(features/hashing.py), including emoji surrogate pairs, collisions, and
padding layout."""

import json
import os

import numpy as np
import pytest

from twtml_tpu.features import Featurizer, Status, native

DATA = os.path.join(os.path.dirname(__file__), "data", "tweets.jsonl")

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native featurizer unavailable (no g++?)"
)


def rows_as_dicts(batch):
    out = []
    for i in range(batch.token_idx.shape[0]):
        row = {}
        for j in range(batch.token_idx.shape[1]):
            if batch.token_val[i, j] != 0:
                row[int(batch.token_idx[i, j])] = float(batch.token_val[i, j])
        out.append(row)
    return out


@pytest.fixture()
def statuses():
    with open(DATA, encoding="utf-8") as fh:
        return [Status.from_json(json.loads(line)) for line in fh if line.strip()]


def test_native_matches_python_on_fixture(statuses):
    feat = Featurizer(now_ms=1785320000000)
    fast = feat._featurize_batch_native(
        [s for s in statuses if feat.filtrate(s)], 0, 0
    )
    assert fast is not None
    # force the python path by pretending native is unavailable
    keep = [s for s in statuses if feat.filtrate(s)]
    from twtml_tpu.features.batch import pad_feature_batch

    slow = pad_feature_batch([feat.featurize(s) for s in keep])
    fast_rows = rows_as_dicts(fast)
    slow_rows = rows_as_dicts(slow)
    for i in range(len(keep)):
        assert fast_rows[i] == slow_rows[i], f"row {i} diverged"
    np.testing.assert_allclose(fast.numeric, slow.numeric, rtol=1e-6)
    np.testing.assert_array_equal(fast.label, slow.label)
    np.testing.assert_array_equal(fast.mask, slow.mask)


def test_native_handles_emoji_and_short_texts():
    feat = Featurizer(now_ms=0)
    cases = ["😀", "a", "", "héllo 😀🚀 wörld", "aa" * 139]
    keep = [
        Status(retweeted_status=Status(text=t, retweet_count=500)) for t in cases
    ]
    fast = feat._featurize_batch_native(keep, 0, 0)
    from twtml_tpu.features.batch import pad_feature_batch

    slow = pad_feature_batch([feat.featurize(s) for s in keep])
    assert rows_as_dicts(fast)[: len(cases)] == rows_as_dicts(slow)[: len(cases)]


def test_collision_accumulation_tiny_mod():
    feat = Featurizer(num_text_features=2, now_ms=0)
    keep = [Status(retweeted_status=Status(text="abcdef", retweet_count=500))]
    fast = feat._featurize_batch_native(keep, 0, 0)
    from twtml_tpu.features.batch import pad_feature_batch

    slow = pad_feature_batch([feat.featurize(s) for s in keep])
    assert rows_as_dicts(fast)[0] == rows_as_dicts(slow)[0]
    assert sum(rows_as_dicts(fast)[0].values()) == 5.0  # 5 bigrams total


def test_uncommon_configs_fall_back():
    feat = Featurizer(normalize_accents=True, now_ms=0)
    assert feat._featurize_batch_native([], 0, 0) is None


def test_over_1024_distinct_terms_falls_back_not_hangs():
    """A tweet with >1024 distinct bigrams must overflow the C scratch table
    gracefully (fallback), never spin (regression for the unbounded probe
    loop)."""
    text = "".join(chr(0x4E00 + i) for i in range(1200))  # 1199 distinct bigrams
    feat = Featurizer(num_text_features=100000, now_ms=0)
    s = Status(retweeted_status=Status(text=text, retweet_count=500))
    assert feat._featurize_batch_native([s], 0, 0) is None  # signals fallback
    # and the public API still yields correct (python-path) features
    batch = feat.featurize_batch([s], pre_filtered=True)
    assert batch.num_valid == 1
    assert int((batch.token_val[0] > 0).sum()) == 1199


def test_multithreaded_path_matches_python(monkeypatch):
    """Exercise the row-parallel C path (n_threads>1 needs >=512 rows to
    clear the per-thread row minimum) against the Python ground truth —
    partitioning, per-thread scratch tables, and slot resets included.
    Mixes empty, single-char, emoji, and long rows across the partitions."""
    monkeypatch.setenv("TWTML_NATIVE_THREADS", "4")
    texts = ["", "a", "😀", "héllo 😀🚀 wörld", "the quick brown fox", "ab" * 120]
    keep = [
        Status(retweeted_status=Status(text=texts[i % len(texts)] + str(i), retweet_count=500))
        for i in range(1024)
    ]
    feat = Featurizer(now_ms=0)
    fast = feat._featurize_batch_native(keep, 0, 0)
    assert fast is not None
    from twtml_tpu.features.batch import pad_feature_batch

    slow = pad_feature_batch([feat.featurize(s) for s in keep])
    assert rows_as_dicts(fast)[: len(keep)] == rows_as_dicts(slow)[: len(keep)]


def test_thread_env_non_integer_falls_back_to_auto(monkeypatch):
    monkeypatch.setenv("TWTML_NATIVE_THREADS", "auto")
    feat = Featurizer(now_ms=0)
    s = Status(retweeted_status=Status(text="hello world", retweet_count=500))
    batch = feat.featurize_batch([s], pre_filtered=True)  # must not raise
    assert batch.num_valid == 1


def test_custom_label_fn_uses_native_hashing_with_python_labels():
    from twtml_tpu.features.sentiment import sentiment_label

    feat = Featurizer(now_ms=0)
    feat.label_fn = sentiment_label
    keep = [
        Status(retweeted_status=Status(text=t, retweet_count=500))
        for t in ("i love this great day", "terrible awful broken mess", "neutral words only")
    ]
    fast = feat._featurize_batch_native(keep, 0, 0)
    assert fast is not None  # label_fn no longer forces the python path
    from twtml_tpu.features.batch import pad_feature_batch

    slow = pad_feature_batch([feat.featurize(s) for s in keep])
    assert rows_as_dicts(fast)[:3] == rows_as_dicts(slow)[:3]
    np.testing.assert_array_equal(fast.label[:3], slow.label[:3])
    assert list(fast.label[:3]) == [1.0, 0.0, 1.0]


# ---------------------------------------------------------------------------
# the build stamp: a library is loaded only when it was built on THIS host
# from the CURRENT sources with the CURRENT flags (mtime is not evidence —
# a copy of the tree carries another machine's -march=native code along)

@pytest.fixture()
def scratch_lib(tmp_path, monkeypatch):
    """Point the loader at a scratch library path with a fresh loader
    state; the real library's state comes back afterwards."""
    real_lib, real_state = native._LIB, (native._lib, native._tried)
    assert native.get_lib() is not None
    monkeypatch.setattr(native, "_LIB", str(tmp_path / "libfasthash.so"))
    native._lib, native._tried = None, False
    builds = []

    def fake_build(stamp):
        # "compile": the real, already-built image + the stamp the loader
        # asked for (no g++ run per test)
        import shutil

        builds.append(stamp)
        shutil.copyfile(real_lib, native._LIB)
        with open(native._LIB + ".stamp", "w", encoding="utf-8") as fh:
            json.dump(stamp, fh)
        return True

    yield fake_build, builds
    native._lib, native._tried = real_state
    native.rebind_flags()


@pytest.mark.parametrize("tamper", ["sources", "flags", "host", "no-stamp"])
def test_foreign_stamp_is_rebuilt_never_loaded(scratch_lib, monkeypatch, tamper):
    fake_build, builds = scratch_lib
    monkeypatch.setattr(native, "_build", fake_build)
    # a library that was NOT built here: garbage bytes (dlopen would fail,
    # like another CPU's instructions would SIGILL) beside a stamp naming
    # other sources / other flags / another host — or no stamp at all
    with open(native._LIB, "wb") as fh:
        fh.write(b"not built on this machine")
    stamp = native.expected_stamp()
    if tamper == "flags":
        stamp["flags"] = [*stamp["flags"], "-mavx512bw"]
    elif tamper != "no-stamp":
        stamp[tamper] = "0" * 64
    if tamper != "no-stamp":
        with open(native._LIB + ".stamp", "w", encoding="utf-8") as fh:
            json.dump(stamp, fh)
    assert native.read_stamp() != native.expected_stamp()
    lib = native.get_lib()
    assert lib is not None  # the garbage was replaced, not dlopen'ed
    assert builds == [native.expected_stamp()]
    assert native.read_stamp() == native.expected_stamp()


def test_matching_stamp_loads_without_a_build(scratch_lib, monkeypatch):
    fake_build, builds = scratch_lib
    fake_build(native.expected_stamp())  # "built here", stamped
    builds.clear()
    monkeypatch.setattr(native, "_build", fake_build)
    assert native.get_lib() is not None
    assert builds == []
    live = native.require_live()
    assert live["symbols"] == list(native.SYMBOLS)


def test_hidden_compiler_means_no_library_and_a_loud_smoke(
        scratch_lib, monkeypatch, tmp_path, caplog):
    """With g++ off PATH nothing can be built: the apps degrade (get_lib()
    is None, WARNING logged) but the measurement gate fails loudly instead
    of timing the Python fallback."""
    import logging

    empty = tmp_path / "empty-bin"
    empty.mkdir()
    monkeypatch.setenv("PATH", str(empty))
    with caplog.at_level(logging.WARNING, logger="twtml_tpu.features.native"):
        assert native.get_lib() is None
    assert any("build failed" in r.message for r in caplog.records)
    assert not os.path.exists(native._LIB)
    with pytest.raises(RuntimeError, match="refusing to measure"):
        native.require_live()
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    import chip_smoke

    with pytest.raises(RuntimeError, match="native fast path is not live"):
        chip_smoke.phase_native()
