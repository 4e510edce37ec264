"""Tokenizer tests: byte-level BPE trainer/encoder/decoder roundtrips.

Parity target: the reference's in-tree tokenizer wrappers
(``python/hetu/data``: GPT2 BPE / HF / sentencepiece / tiktoken)."""

import numpy as np
import pytest

from hetu_tpu.data.tokenizers import (
    ByteLevelBPETokenizer, bytes_to_unicode, train_bpe,
)

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "the quick brown fox likes the lazy dog",
    "hello world, hello tokenizer world",
    "don't stop believing 12345",
] * 8


def test_bytes_to_unicode_is_bijective():
    m = bytes_to_unicode()
    assert len(m) == 256 and len(set(m.values())) == 256


@pytest.fixture(scope="module")
def tok():
    return train_bpe(CORPUS, vocab_size=350)


def test_train_bpe_learns_merges(tok):
    assert len(tok.merge_ranks) > 0
    assert 256 < tok.vocab_size <= 350
    # frequent words compress below byte length
    ids = tok.encode("the quick brown fox")
    assert len(ids) < len("the quick brown fox".encode())


def test_roundtrip_exact(tok):
    for text in ["hello world", "don't stop!", "  spaces   and\ttabs\n",
                 "unicode: héllo wörld ünïcode", "数字 and 中文 mix"]:
        assert tok.decode(tok.encode(text)) == text


def test_roundtrip_unseen_bytes(tok):
    # byte fallback covers symbols never in the corpus
    text = "\x00\x7f\xff émoji: 🙂"
    assert tok.decode(tok.encode(text)) == text


def test_save_load_identical(tok, tmp_path):
    tok.save(str(tmp_path))
    tok2 = ByteLevelBPETokenizer.from_files(
        str(tmp_path / "vocab.json"), str(tmp_path / "merges.txt"),
        special_tokens=tok.special)
    for text in CORPUS[:4]:
        assert tok2.encode(text) == tok.encode(text)
    assert tok2.decode(tok.encode(CORPUS[0])) == CORPUS[0]


def test_special_tokens(tok):
    eot = tok.special["<|endoftext|>"]
    assert tok.decode([eot]) == "<|endoftext|>"
    assert eot == tok.vocab_size - 1


def test_feeds_dataset(tok, tmp_path):
    """Tokenizer plugs into JsonDataset as the reference's wrappers do."""
    import json
    from hetu_tpu.data.dataset import JsonDataset
    p = tmp_path / "d.jsonl"
    with open(p, "w") as f:
        for t in CORPUS[:3]:
            f.write(json.dumps({"text": t}) + "\n")
    ds = JsonDataset(str(p), tokenizer=tok)
    assert len(ds) == 3
    assert ds[0].dtype == np.int32 and len(ds[0]) > 0
    assert tok.decode(ds[0].tolist()) == CORPUS[0]


def test_encode_emits_special_ids(tok):
    eot = tok.special["<|endoftext|>"]
    ids = tok.encode("hello<|endoftext|>world")
    assert eot in ids
    assert tok.decode(ids) == "hello<|endoftext|>world"
    assert tok.encode("<|endoftext|>") == [eot]


def test_cache_eviction_mid_encode_regression(tok):
    """Eviction must not strand placeholder words recorded before the
    clear: encode() caches 'hello', then a call whose NEW words push the
    cache over the limit must still resolve the already-cached 'hello'
    (old code cleared inside _encode_words and KeyError'd)."""
    old = tok._cache_limit
    try:
        tok._id_cache.clear()
        tok.encode("hello world")          # seeds the cache
        tok._cache_limit = 1               # next encode triggers eviction
        ids = tok.encode("hello fox dog quick brown")
        assert tok.decode(ids) == "hello fox dog quick brown"
    finally:
        tok._cache_limit = old


def test_save_load_preserves_specials(tok, tmp_path):
    tok.save(str(tmp_path))
    tok2 = ByteLevelBPETokenizer.from_files(
        str(tmp_path / "vocab.json"), str(tmp_path / "merges.txt"))
    ids = tok.encode("a<|endoftext|>b")
    assert tok2.decode(ids) == "a<|endoftext|>b"
    assert tok2.encode("a<|endoftext|>b") == ids


_ROOTS = ["inter", "nation", "token", "transform", "comput",
          "distribut", "paralleliz", "check", "point", "attent"]
_SUFS = ["ation", "izer", "ing", "ed", "ment", "ational", "ism",
         "istic", "ality"]


def _native_and_python_bpe():
    """One trained tokenizer twice: with the C++ merge core
    (csrc/bpe.cpp) and forced onto the pure-Python loop (a fresh
    instance, cold caches). Skips without a native toolchain."""
    import random

    import pytest

    from hetu_tpu.data.tokenizers import _bpe_lib

    rng = random.Random(0)
    corpus = [" ".join(rng.choice(_ROOTS) + rng.choice(_SUFS)
                       for _ in range(200)) for _ in range(100)]
    corpus += ["ragnarök — prélude, 北京 2024!"] * 5
    tok = train_bpe(corpus, vocab_size=2500)
    if _bpe_lib() is None:
        pytest.skip("no native toolchain")
    assert tok._native is not None
    tok_py = ByteLevelBPETokenizer(
        tok.vocab, sorted(tok.merge_ranks, key=tok.merge_ranks.get),
        special_tokens=tok.special)
    tok_py._native = None
    return tok, tok_py


def test_native_bpe_parity():
    """The C++ merge core must produce byte-identical ids to the
    pure-Python loop, and both round-trip."""
    tok, tok_py = _native_and_python_bpe()
    text = ("supercalifragilistic internationalization 北京 prélude "
            "the quick brown fox! " * 20)
    native_ids = tok.encode(text)
    assert native_ids == tok_py.encode(text)
    assert tok.decode(native_ids) == text
    assert tok_py.decode(native_ids) == text


def test_native_bpe_is_not_slower():
    """On merge-heavy fresh words (numeric tails defeat the cache) the
    batched native call must not be meaningfully slower than the Python
    merge loop (typical measured: ~1.4x faster; the authoritative
    timing lives in workloads/). Compared in THIS thread's CPU time,
    best of three, with a generous ratio: wall-clock time loses to six
    busy xdist workers, CPU time does not see them."""
    import random
    import time

    tok, tok_py = _native_and_python_bpe()
    rng = random.Random(1)
    blob = " ".join(rng.choice(_ROOTS) + rng.choice(_SUFS)
                    + str(rng.randint(0, 10 ** 6)) for _ in range(8000))

    def cpu_seconds(fn):
        t0 = time.thread_time()
        fn()
        return time.thread_time() - t0

    t_native = min(cpu_seconds(
        lambda: (tok._id_cache.clear(), tok.encode(blob)))
        for _ in range(3))
    t_py = min(cpu_seconds(
        lambda: (tok_py._id_cache.clear(), tok_py._cache.clear(),
                 tok_py.encode(blob))) for _ in range(3))
    assert tok.encode(blob) == tok_py.encode(blob)
    assert t_native < 3.0 * t_py, (t_native, t_py)


def test_tiktoken_wrapper_roundtrip():
    """tiktoken wrapper parity (reference wraps tiktoken in
    ``python/hetu/data``): byte-exact roundtrip + the gpt2 encoding
    agrees with our in-tree byte-level BPE id space size."""
    pytest.importorskip("tiktoken")
    from hetu_tpu.data.tokenizers import TiktokenTokenizer

    try:
        tok = TiktokenTokenizer("gpt2")
    except Exception as e:   # encoding file fetch needs network/cache
        pytest.skip(f"tiktoken gpt2 encoding unavailable offline "
                    f"({type(e).__name__})")
    text = "hello world — ragnarök 北京 <|endoftext|> tail"
    ids = tok.encode(text)
    assert tok.decode(ids) == text
    assert tok.vocab_size == 50257


def test_sentencepiece_wrapper_gated():
    """Absent optional dep raises a CLEAR ImportError (not a bare
    ModuleNotFoundError deep in a call)."""
    from hetu_tpu.data.tokenizers import SentencePieceTokenizer
    try:
        import sentencepiece  # noqa: F401
        pytest.skip("sentencepiece installed — gating not exercisable")
    except ImportError:
        pass
    with pytest.raises(ImportError, match="sentencepiece"):
        SentencePieceTokenizer("/nonexistent.model")
