"""Seeded status-line generator: the one general generator every mix's
parameters (``benchmark/traffic/<mix>.json`` → ``"generator"``) drive.

A line is a retweet in the v1.1 streaming API's compact JSON, stripped to
the fields the featurizer reads, non-ASCII written ``\\uXXXX``-escaped. The
generator works in chunks of ``CHUNK`` lines, each chunk from its own
``(seed, chunk)`` stream, so the feeder (all chunks), the reference (the
first few) and the serving clients (any) make the same lines without
passing bytes between processes. Beside the bytes a chunk carries the TRUTH
it was written from (texts as Python strings, the numeric columns, whether
the filter keeps the line): the reference reads that, never the JSON, so the
program's parser is held to what the generator meant.

Imports NumPy and the standard library only.
"""

from __future__ import annotations

import dataclasses

import numpy as np

CHUNK = 16384   # lines per generation unit
FILL = 26       # filler letters a line can need (the longest token is 24)

_LATIN = "abcdefghijklmnopqrstuvwxyz"
# letter frequencies, roughly English, so bigrams are not uniform
_LATIN_P = np.array([
    8.2, 1.5, 2.8, 4.3, 12.7, 2.2, 2.0, 6.1, 7.0, 0.15, 0.8, 4.0, 2.4, 6.7,
    7.5, 1.9, 0.1, 6.0, 6.3, 9.1, 2.8, 1.0, 2.4, 0.15, 2.0, 0.07,
])
_ACCENTED = "áàâäãåçéèêëíìîïñóòôöõúùûüýÿ"
_ACCENTED_UPPER = "ÉÀÖÜÑÇ"  # lowered by str.lower(), as Java's toLowerCase does
_CJK = (0x4E00, 0x9FA5)
_EMOJI = (0x1F600, 0x1F64F)  # astral: two UTF-16 units each


def slots(g: dict) -> int:
    """Word slots drawn per line: enough that whole words always run out
    before the slots do (a word and its space take >= 3 units), so the
    filler never has more than one token's length to make up. 48 for the
    classic 140 units, 95 for 280."""
    return -(-int(g["text_units_max"]) // 3) + 1


def _units(s: str) -> int:
    return len(s.encode("utf-16-le", "surrogatepass")) // 2


def _escape(s: str) -> str:
    """JSON string body as the v1.1 API writes it: ASCII as is (tokens hold
    no quote, backslash or control character), the rest ``\\uXXXX`` per
    UTF-16 unit."""
    if s.isascii():
        return s
    b = s.encode("utf-16-le", "surrogatepass")
    out = []
    for i in range(0, len(b), 2):
        u = b[i] | (b[i + 1] << 8)
        out.append(chr(u) if u < 128 else f"\\u{u:04x}")
    return "".join(out)


@dataclasses.dataclass
class Vocab:
    tokens: list          # Python strings
    escaped: list         # their JSON string bodies
    units: np.ndarray     # UTF-16 length of each
    ascii_ids: np.ndarray     # ids of the all-ASCII tokens
    other_ids: np.ndarray     # ids of tokens with a non-ASCII character
    cdf_all: np.ndarray       # Zipf cdf over all ids, in id order
    cdf_ascii: np.ndarray     # Zipf cdf over ascii_ids
    cdf_other: np.ndarray


def _zipf_p(n: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return p / p.sum()


def _zipf_cdf(n: int, a: float) -> np.ndarray:
    return np.cumsum(_zipf_p(n, a))


def _place_lexicon(g: dict, seed: int, tokens: list) -> None:
    """The mix's ``generator.lexicon``: ``{"positive": [words], "negative":
    [words], "slot_share_positive": p, "slot_share_negative": q}`` — the
    source of a label that is read from the TEXT (a learner whose label is a
    word count; the rule itself is the program's and the reference's, each
    its own copy: here are only the words, as data of the mix).

    Each listed word takes the place of an all-ASCII token OF THE SAME
    LENGTH, so every token keeps its units, its ASCII-ness and its rank: a
    line's length, its word count and its filler are the lexicon-free mix's,
    hence a block's multiset of text lengths and every compiled shape. The
    ranks are chosen so that a list's words together hold ``p`` (``q``) of
    the word slots: a slot's chance of token ``id`` is its Zipf mass among
    the ASCII tokens in an all-ASCII tweet and among all tokens in the
    ``non_ascii_tweet_share`` others. Words are dealt in an order drawn from
    the seed (a stream of its own: the vocabulary's and the lines' draws are
    untouched), each to the free token of its length whose mass is nearest
    to an equal part of what its list still lacks."""
    lex = g["lexicon"]
    rng = np.random.default_rng([int(seed), 0x6C6578])
    a = float(g["zipf_exponent"])
    s = float(g["non_ascii_tweet_share"])
    ids = np.flatnonzero([t.isascii() for t in tokens])
    length = np.array([len(tokens[i]) for i in ids])
    mass = (1 - s) * _zipf_p(len(ids), a) + s * _zipf_p(len(tokens), a)[ids]
    free = np.ones(len(ids), dtype=bool)
    both = list(lex["positive"]) + list(lex["negative"])
    if len(set(both)) != len(both) or not all(
            w.isascii() and w.islower() and " " not in w for w in both):
        raise SystemExit("benchmark: a lexicon lists distinct lower-case "
                         "ASCII words, each in one list")
    for words, share in ((lex["positive"], lex["slot_share_positive"]),
                         (lex["negative"], lex["slot_share_negative"])):
        lacks = float(share)
        for dealt, k in enumerate(rng.permutation(len(words))):
            w = words[k]
            fits = np.flatnonzero(free & (length == len(w)))
            if not fits.size:
                raise SystemExit(f"benchmark: no free ASCII token of "
                                 f"{len(w)} units for the lexicon's {w!r}")
            part = max(lacks / (len(words) - dealt), mass.min())
            j = fits[np.argmin(np.abs(np.log(mass[fits] / part)))]
            tokens[ids[j]], free[j] = w, False
            lacks -= mass[j]


def run_lines(g: dict, seed: int, chunk: int, target: np.ndarray,
              kept: np.ndarray) -> dict:
    """The mix's ``generator.runs``: ``{"every_blocks": k, "lines_per_block":
    m, "min_units": u, "chars": [c, ...]}`` — the tweet that is ONE character
    over and over ("kkkkk…", "!!!!!…", "。。。…"), which every live sample
    holds. In every block of ``length_block`` lines whose index in the pool
    is a multiple of ``k``, the first ``m`` KEPT lines whose dealt length L
    is at least ``u`` have their text replaced by L units of one character of
    ``chars``, drawn per line from a stream of its own (the lines' draws are
    untouched): L − 1 identical bigrams in one row. → ``{line: character}``
    for this chunk's lines.

    Only the text changes: the line keeps its length, so a block keeps its
    multiset of text lengths, hence every compiled shape under every seed;
    the numeric columns and the label are what they were. ``chars`` are
    single UTF-16 units, lower-case (the reference lower-cases, and a
    character whose lower case is another would still be one run). A block
    with fewer than ``m`` such lines is an error, never a silent skip:
    ``lint_runs`` finds it without a seed, since a block's lengths and kept
    lines are the same multiset under every seed."""
    runs = g["runs"]
    block = int(g["length_block"])
    every, per = int(runs["every_blocks"]), int(runs["lines_per_block"])
    chars = list(runs["chars"])
    if CHUNK % block or every < 1 or per < 1 or not chars or not all(
            isinstance(c, str) and _units(c) == 1 and c == c.lower()
            and c.isprintable() and c not in '"\\' for c in chars):
        raise SystemExit(
            "benchmark: generator.runs needs a length_block that divides "
            f"{CHUNK}, every_blocks and lines_per_block of at least 1, and "
            "chars of one printable lower-case UTF-16 unit each")
    rng = np.random.default_rng([int(seed), 0x72756E, int(chunk)])
    out = {}
    for b in range(0, len(target), block):
        if (chunk * CHUNK + b) // block % every:
            continue
        fit = b + np.flatnonzero(kept[b:b + block] & (
            target[b:b + block] >= int(runs["min_units"])))[:per]
        if fit.size < per:
            raise SystemExit(
                f"benchmark: generator.runs asks for {per} kept line(s) of "
                f"{runs['min_units']} units or more in block "
                f"{(chunk * CHUNK + b) // block}, which holds {fit.size}")
        for i, c in zip(fit, rng.integers(0, len(chars), per)):
            out[int(i)] = chars[c]
    return out


def lint_runs(g: dict) -> list:
    """The faults of a mix's ``runs`` object, as strings: made without a
    seed's pool (the lengths' stream and the kept lines of a block take no
    seed), over every chunk of the pool."""
    if not g.get("runs"):
        return []
    n = int(g["pool_lines"])
    try:
        for c in range((n + CHUNK - 1) // CHUNK):
            target, kept = _dealt(g, np.random.default_rng(0), c,
                                  min(CHUNK, n - c * CHUNK))
            run_lines(g, 0, c, target, kept)
    except SystemExit as exc:
        return [str(exc)]
    except (KeyError, TypeError, ValueError) as exc:
        return [f"benchmark: generator.runs is not the object gen.run_lines "
                f"reads: {exc!r}"]
    return []


def build_vocab(g: dict, seed: int) -> Vocab:
    rng = np.random.default_rng([int(seed), 0x766F63])
    n = int(g["vocab_size"])
    lens = rng.integers(2, 13, n)
    letters = rng.choice(26, size=int(lens.sum()), p=_LATIN_P / _LATIN_P.sum())
    kind = rng.random(n)
    extra = rng.random(n)
    cp = rng.integers(0, 1 << 30, (n, 4))
    tokens = []
    pos = 0
    share_other = float(g["vocab_non_ascii_share"])
    for i in range(n):
        w = "".join(_LATIN[j] for j in letters[pos:pos + lens[i]])
        pos += lens[i]
        k = kind[i]
        if k < share_other:
            sub = extra[i]
            if sub < 0.5:      # accented Latin word
                j = cp[i, 0] % len(w)
                acc = (_ACCENTED_UPPER[cp[i, 1] % len(_ACCENTED_UPPER)]
                       if cp[i, 2] % 10 == 0
                       else _ACCENTED[cp[i, 1] % len(_ACCENTED)])
                w = w[:j] + acc + w[j + 1:]
            elif sub < 0.8:    # CJK token, 1-4 characters
                w = "".join(
                    chr(_CJK[0] + int(c) % (_CJK[1] - _CJK[0]))
                    for c in cp[i, :1 + cp[i, 3] % 4]
                )
            else:              # one or two emoji (surrogate pairs)
                w = "".join(
                    chr(_EMOJI[0] + int(c) % (_EMOJI[1] - _EMOJI[0]))
                    for c in cp[i, :1 + cp[i, 3] % 2]
                )
        elif extra[i] < 0.08:
            w = w.capitalize()
        elif extra[i] < 0.11:
            w = "#" + w
        elif extra[i] < 0.14:
            w = "@" + w
        elif extra[i] < 0.15:
            w = "http://t.co/" + w
        tokens.append(w)
    if g.get("lexicon"):   # absent: not one draw more, the pool as it was
        _place_lexicon(g, seed, tokens)
    is_ascii = np.array([t.isascii() for t in tokens])
    a = float(g["zipf_exponent"])
    ascii_ids = np.flatnonzero(is_ascii)
    other_ids = np.flatnonzero(~is_ascii)
    return Vocab(
        tokens=tokens,
        escaped=[_escape(t) for t in tokens],
        units=np.array([_units(t) for t in tokens], dtype=np.int64),
        ascii_ids=ascii_ids, other_ids=other_ids,
        cdf_all=_zipf_cdf(n, a),
        cdf_ascii=_zipf_cdf(len(ascii_ids), a),
        cdf_other=_zipf_cdf(len(other_ids), a),
    )


@dataclasses.dataclass
class Chunk:
    """``n`` generated lines and the truth they were written from."""
    lines: list            # JSON text, no terminator
    text: list             # the ORIGINAL tweet's text (Python str)
    followers: np.ndarray
    favourites: np.ndarray
    friends: np.ndarray
    created_ms: np.ndarray
    retweets: np.ndarray   # the original's retweet_count = label
    kept: np.ndarray       # bool: inside the filter's interval


def _dealt(g: dict, rng, chunk: int, n: int) -> tuple:
    """``(target, kept_of_block)`` of a chunk's ``n`` lines: each line's text
    length and whether the filter keeps it, dealt out inside blocks of
    ``length_block`` lines by ``rng`` (the chunk's line stream: one
    permutation a block, its first draws)."""
    # Text lengths come from a stream the SEED DOES NOT ENTER, fixed per
    # chunk; the seed only deals them out inside blocks of `length_block`
    # lines. So every seed's batches hold the same totals of code units (the
    # ragged wire's bucket, hence the compiled shapes), in another order.
    lengths = np.random.default_rng([0x6C656E, int(chunk)])
    target = np.clip(
        np.rint(lengths.normal(g["text_units_mean"], g["text_units_sd"], n)),
        g["text_units_min"], g["text_units_max"],
    ).astype(np.int64)
    block = int(g["length_block"])
    share = float(g["keep_share"])
    # Which lines pass the filter is fixed the same way when the share is
    # under 1: the first round(share * block) entries of every block's
    # seed-independent stream, dealt out with the lengths. Every block then
    # holds the same kept lines of the same lengths under every seed, and a
    # mix whose batch is a whole number of blocks' kept lines keeps the
    # same-shapes property.
    kept_of_block = np.arange(n) % block < round(share * block)
    for b in range(0, n, block):
        deal = rng.permutation(len(target[b:b + block]))
        target[b:b + block] = target[b:b + block][deal]
        kept_of_block[b:b + block] = kept_of_block[b:b + block][deal]
    return target, kept_of_block


def make_chunk(g: dict, vocab: Vocab, seed: int, chunk: int,
               n: int = CHUNK) -> Chunk:
    rng = np.random.default_rng([int(seed), 0x6C696E, int(chunk)])
    now_ms = int(g["now_ms"])
    target, kept_of_block = _dealt(g, rng, chunk, n)
    share = float(g["keep_share"])
    non_ascii = rng.random(n) < float(g["non_ascii_tweet_share"])
    n_slots = slots(g)
    ids = vocab.ascii_ids[
        np.searchsorted(vocab.cdf_ascii, rng.random((n, n_slots)))
    ]
    rows = np.flatnonzero(non_ascii)
    if rows.size:
        ids[rows] = np.searchsorted(
            vocab.cdf_all, rng.random((rows.size, n_slots))
        )
        # at least one non-ASCII token, in one of the first two slots
        ids[rows, rng.integers(0, 2, rows.size)] = vocab.other_ids[
            np.searchsorted(vocab.cdf_other, rng.random(rows.size))
        ]
    # whole words while they fit, then filler letters to the exact length
    cum = np.cumsum(vocab.units[ids] + 1, axis=1) - 1
    n_words = (cum <= target[:, None]).sum(axis=1)
    used = np.where(n_words > 0, cum[np.arange(n), np.maximum(n_words, 1) - 1], 0)
    rest = target - used
    filler = rng.choice(26, size=(n, FILL))
    n_units = target

    followers = rng.integers(100, 2_000_000, n)
    favourites = rng.integers(0, 50_000, n)
    friends = rng.integers(0, 5_000, n)
    created = now_ms - rng.integers(60_000, 7 * 86_400_000, n)
    # a noisy linear function of the features (SyntheticSource's shape)
    raw = 100 + followers * 4e-4 + n_units * 2 + rng.normal(0, 20, n)
    rng.random(n)   # (the draw a random keep took: the stream stays as it was)
    keep = kept_of_block if share < 1.0 else np.ones(n, dtype=bool)
    # absent: not one draw more, the pool as it was
    run_of = run_lines(g, seed, chunk, target, keep) if g.get("runs") else {}
    retweets = np.where(
        keep,
        np.clip(np.rint(raw), g["retweets_min"], g["retweets_max"]),
        rng.integers(0, int(g["retweets_min"]), n),  # below the interval
    ).astype(np.int64)
    rt_followers = rng.integers(0, 5_000, n)
    names = ids[:, n_slots - 1]

    tok, esc = vocab.tokens, vocab.escaped
    lines, texts = [], []
    for i in range(n):
        row = ids[i, :n_words[i]]
        r = rest[i]
        if r == 0:
            tail = ""
        elif len(row) == 0 or r == 1:   # no room for a space and a word
            tail = "".join([_LATIN[j] for j in filler[i, :r]])
        else:
            tail = " " + "".join([_LATIN[j] for j in filler[i, :r - 1]])
        if i in run_of:   # one character, L times; the line's length stays
            text = run_of[i] * int(target[i])
            body = _escape(text)
        else:
            text = " ".join([tok[j] for j in row]) + tail
            body = (text if not non_ascii[i]
                    else " ".join([esc[j] for j in row]) + tail)
        texts.append(text)
        lines.append(
            f'{{"text":"RT @u{names[i]}: {body}","retweet_count":0,'
            f'"user":{{"followers_count":{rt_followers[i]},'
            f'"favourites_count":0,"friends_count":0}},'
            f'"timestamp_ms":"{now_ms}","lang":"en",'
            f'"retweeted_status":{{"text":"{body}",'
            f'"retweet_count":{retweets[i]},'
            f'"user":{{"followers_count":{followers[i]},'
            f'"favourites_count":{favourites[i]},'
            f'"friends_count":{friends[i]}}},'
            f'"timestamp_ms":"{created[i]}","lang":"en"}}}}'
        )
    kept = (retweets >= g["retweets_min"]) & (retweets <= g["retweets_max"])
    return Chunk(lines, texts, followers, favourites, friends, created,
                 retweets, kept)


def predict_rows(chunk: Chunk, rows) -> list:
    """The ``/api/predict`` request rows for lines ``rows`` of a chunk: the
    original tweet's text and author numbers, as the serving API takes
    them."""
    return [
        {
            "text": chunk.text[i],
            "followers_count": int(chunk.followers[i]),
            "favourites_count": int(chunk.favourites[i]),
            "friends_count": int(chunk.friends[i]),
            "created_at_ms": int(chunk.created_ms[i]),
        }
        for i in rows
    ]
