"""Native C++ BPE engine parity + build machinery.

The native merge engine (native/fast_bpe.cpp) must match the Python
reference (data/tokenizer_bpe.py _bpe + vocab lookup) token-for-token —
the Python side is itself HF-oracle-tested (test_tokenizers.py), so
transitively the native path is HF-aligned too. Reference analog:
core/test_tokenizer_bpe.cpp parity cases against the C++ tokenizer.
"""

import os
import shutil

import numpy as np
import pytest

from tests.fixtures import WIKI_LINES, train_tiny_gpt2_tokenizer

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no g++ in environment")


@pytest.fixture(scope="module")
def tok_pair(tmp_path_factory):
    """(native-enabled, python-only) tokenizers over the same tiny vocab."""
    from mobilefinetuner_tpu.data.tokenizer_bpe import GPT2BPETokenizer
    d = str(tmp_path_factory.mktemp("tok"))
    train_tiny_gpt2_tokenizer(d)
    native = GPT2BPETokenizer.from_pretrained(d)
    if native._native is None:
        pytest.skip("native BPE library failed to build")
    python = GPT2BPETokenizer.from_pretrained(d, use_native=False)
    return native, python


def test_native_library_builds():
    if os.environ.get("MFT_NO_NATIVE_BPE") == "1":
        pytest.skip("native BPE disabled by env")
    from mobilefinetuner_tpu.native import fast_bpe
    from mobilefinetuner_tpu.native.build import library_path
    assert fast_bpe.load_library() is not None
    # the library on disk is named by its source's hash: one built from
    # any other fast_bpe.cpp (a stale checkout, a copied disk) can
    # never be the one that loads
    hashed = library_path(fast_bpe._SRC, fast_bpe._LIB)
    assert hashed != fast_bpe._LIB and os.path.exists(hashed)


def test_native_matches_python_on_corpus(tok_pair):
    native, python = tok_pair
    text = "\n".join(WIKI_LINES)
    assert native.encode(text) == python.encode(text)


def test_native_matches_python_on_hard_cases(tok_pair):
    native, python = tok_pair
    cases = [
        "hello world", "  double  spaces  ", "don't stop",
        "Prices rose 3.5% to $1,234.56!", "naïve café über",
        "emoji 🙂 and 中文 bytes", "a", "", "\n\n\t",
        "CamelCaseWords and snake_case_words",
        "<|endoftext|> special <|endoftext|>",
        "x" * 300,  # long single word: deep merge recursion
    ]
    for c in cases:
        assert native.encode(c) == python.encode(c), c


def test_native_matches_python_on_random_bytes(tok_pair):
    native, python = tok_pair
    rng = np.random.default_rng(0)
    for _ in range(50):
        raw = bytes(rng.integers(0, 256, rng.integers(1, 64)))
        text = raw.decode("utf-8", errors="replace")
        assert native.encode(text) == python.encode(text)


def test_env_var_disables_native(tmp_path, monkeypatch):
    monkeypatch.setenv("MFT_NO_NATIVE_BPE", "1")
    from mobilefinetuner_tpu.native import fast_bpe
    # the env check runs before the shared cache lookup (native/build.py),
    # so no cache reset is needed
    assert fast_bpe.load_library() is None


def test_native_is_faster_on_uncached_words(tok_pair):
    """The point of the native path: the merge loop on fresh words. Not a
    strict benchmark — asserts only a sane ratio to catch pathological
    regressions (full numbers: tools/bench_tokenizer.py)."""
    import time
    native, python = tok_pair
    rng = np.random.default_rng(1)
    # unique pseudo-words defeat the per-word cache
    words = [" w" + "".join(chr(97 + c) for c in rng.integers(0, 26, 12))
             for _ in range(3000)]
    text = "".join(words)

    t0 = time.perf_counter()
    out_n = native.encode(text)
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_p = python.encode(text)
    t_python = time.perf_counter() - t0
    assert out_n == out_p
    assert t_native < t_python * 1.5, (t_native, t_python)