"""The benchmark's traffic source: Table 4 mixes, seeded the same in
every process."""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import corpus  # noqa: E402
import ingest  # noqa: E402

SEED = 2**31 + 12345

DIGEST = """
import hashlib, sys
sys.path.insert(0, {chip!r})
import corpus, ingest
h = hashlib.md5()
for table, prof in (("lipsum", "arabic"), ("lipsum", "emoji"),
                    ("wiki", "thai")):
    h.update(corpus.text(table, prof, 1 << 16, {seed})[0].tobytes())
pool = ingest.make_pool({traffic!r}, {seed})
for d in pool.docs:
    h.update(d.tobytes())
print(h.hexdigest())
"""

TRAFFIC = {"table": "wiki", "profiles": ["arabic", "english", "hindi"],
           "pool_docs": 64, "length": {"alpha": 1.2, "scale": 300,
                                       "cap": 4096},
           "invalid_share": 0.125, "malformed": ["80", "c2", "ef bf", "fe",
                                                  "c0 af", "ed a0 80"]}


def test_same_bytes_whatever_the_hash_seed():
    """Two interpreters with different string hashing make the same
    inputs from one seed."""
    code = DIGEST.format(chip=str(CHIP), seed=SEED, traffic=TRAFFIC)
    out = []
    for hs in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hs)
        out.append(subprocess.run([sys.executable, "-c", code], env=env,
                                  capture_output=True, text=True,
                                  check=True, timeout=120).stdout.strip())
    assert out[0] == out[1] and len(out[0]) == 32


def test_other_seed_other_bytes():
    a = corpus.text("lipsum", "hindi", 4096, SEED)[0]
    b = corpus.text("lipsum", "hindi", 4096, SEED + 1)[0]
    assert not np.array_equal(a, b)


CASES = [(t, p) for t in corpus.TABLES for p in corpus.TABLES[t]]


@pytest.mark.parametrize("table,profile", CASES)
def test_length_shares_match_table4(table, profile):
    """Each UTF-8 length class's share of characters is Table 4's within
    five standard errors of a sample of 200,000."""
    n = 200_000
    cp = corpus.codepoints(table, profile, n, corpus.rng_for(SEED, table))
    got = np.bincount(corpus.utf8_lengths(cp) - 1, minlength=4) / n
    want = np.asarray(corpus.TABLES[table][profile][0]) / 100.0
    tol = 5 * np.sqrt(want * (1 - want) / n) + 1e-9
    assert np.all(np.abs(got - want) <= tol), (got, want)


@pytest.mark.parametrize("profile", ["latin", "russian", "chinese",
                                     "emoji"])
def test_text_is_valid_utf8_of_exact_size(profile):
    buf, chars = corpus.text("lipsum", profile, 10_001, SEED)
    text = buf.tobytes().decode("utf-8")
    assert buf.shape[0] == 10_001 and len(text) == chars
    cp = np.frombuffer(text.encode("utf-32-le"), np.uint32)
    np.testing.assert_array_equal(corpus.utf8_encode(cp), buf)


def test_cut_documents_on_character_boundaries():
    stream, _ = corpus.text("lipsum", "japanese", 50_000, SEED)
    docs = corpus.cut_documents(stream, np.full(40, 1000))
    for d in docs:
        d.tobytes().decode("utf-8")
        assert 997 <= d.shape[0] <= 1000


MALFORMED = json.loads((CHIP / "traffic" / "wiki-replace-heavy.json")
                       .read_text())["malformed"]


@pytest.mark.parametrize("seq", MALFORMED)
def test_malformed_copy_holds_the_sequence_and_is_invalid(seq):
    buf, _ = corpus.text("lipsum", "arabic", 2048, SEED)
    bad = corpus.malform(buf, corpus.rng_for(SEED, "m"), [seq])
    with pytest.raises(UnicodeDecodeError):
        bad.tobytes().decode("utf-8")
    raw = bytes.fromhex(seq)
    assert bad.shape[0] == buf.shape[0] + len(raw)
    # Inserted whole at a character boundary: the rest is the original.
    n = len(raw)
    starts = [a for a in range(buf.shape[0] + 1)
              if a == buf.shape[0] or (buf[a] & 0xC0) != 0x80]
    assert any(bad[a:a + n].tobytes() == raw
               and np.array_equal(np.concatenate([bad[:a], bad[a + n:]]), buf)
               for a in starts)
    buf.tobytes().decode("utf-8")           # the original is untouched


def test_pool_counts_are_cpythons():
    pool = ingest.make_pool(TRAFFIC, SEED)
    assert len(pool.docs) == TRAFFIC["pool_docs"]
    for d, c, u in zip(pool.docs, pool.chars, pool.units):
        text = d.tobytes().decode("utf-8", "replace")
        assert (c, u) == (len(text), len(text.encode("utf-16-le")) // 2)
    assert sum(1 for d in pool.docs if not _valid(d)) == 8


def test_batches_fill_the_budget_with_whole_documents():
    pool = ingest.make_pool(TRAFFIC, SEED)
    batches = ingest.Batches(pool, 16, SEED)
    seen = []
    for _ in range(20):
        idx = batches.next()
        t = int(pool.tiles[idx].sum())
        assert 0 < t <= 16 and len(idx) > 0
        seen.extend(idx.tolist())
    # Every document comes round once per pass.
    first = seen[:len(pool.docs)]
    assert sorted(first) == list(range(len(pool.docs)))


def _valid(d) -> bool:
    try:
        d.tobytes().decode("utf-8")
        return True
    except UnicodeDecodeError:
        return False
