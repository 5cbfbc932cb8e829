"""Differential tests: C++ NativeBPETokenizer vs HF's Rust `tokenizers`.

The N7 parity contract (SURVEY §2b): the reference tokenizes through the Rust
HF tokenizer (train_distributed.py:46; distributed_actor.py:217–229). Here a
byte-level BPE is TRAINED at test time with the `tokenizers` library using the
exact Qwen2 tokenizer.json configuration (NFC normalizer + cl100k-style Split
regex + ByteLevel), saved as tokenizer.json, and the C++ core must reproduce
the Rust encode/decode exactly — including the \\p{N}{1,3} digit chunking and
newline alternatives the round-1 GPT-2 approximation got wrong.
"""

import json

import pytest

tokenizers = pytest.importorskip("tokenizers")

from distrl_llm_tpu.native.build import native_available
from distrl_llm_tpu.native.tokenizer import NativeBPETokenizer, _detect_pretok_kind

pytestmark = pytest.mark.skipif(
    not native_available(), reason="g++ not available"
)

# The Qwen2/Qwen2.5 pre_tokenizer Split regex, verbatim from the checkpoint
# family's tokenizer.json.
QWEN2_PATTERN = (
    r"(?i:'s|'t|'re|'ve|'m|'ll|'d)|[^\r\n\p{L}\p{N}]?\p{L}+|\p{N}{1,3}"
    r"| ?[^\s\p{L}\p{N}]+[\r\n]*|\s*[\r\n]+|\s+(?!\S)|\s+"
)

CORPUS = [
    "The quick brown fox jumps over the lazy dog. 12345 + 67890 = 80235.",
    "Solve for x: 3x^2 - 14x + 8 = 0. The answer is x = 4 or x = 2/3.",
    "<think>\nLet me compute 144 * 233 = 33552.\n</think>\n<answer>33552</answer>",
    "héllo wörld — naïve café résumé",
    "数学问题：计算 1234 + 5678 的值。答案是 6912。",
    "I'll say we're done, it's fine, you've won, I'd agree, they'd'VE",
    "def f(x):\n    return x**2  # comment\n\n\nprint(f(10))",
    "line one\nline two\r\nline three\n\n\nend   ",
    "π ≈ 3.14159, e ≈ 2.71828; φ = (1+√5)/2",
]

TRICKY = [
    "12345678901234567890",          # digit chunking \p{N}{1,3}
    "1,234,567.89 and -42",
    "a\n\nb",                        # newline alternatives
    "x \n \n y",                     # mixed space/newline runs
    "   leading and trailing   ",
    "tabs\tand nbsp　ideographic",
    "I'LL DON'T can'T THEY'RE",      # case-insensitive contractions
    "(hello)[world]{math}",          # joiner char + letter runs
    "héllo wörld 数学 ١٢٣ ៥៦",       # multilingual letters + non-ASCII digits
    "e = mc²; x₁ + x₂",
    "<|im_start|>user\n2+2?<|im_end|>\n<|im_start|>assistant\n",
    "emoji 🙂 test 🎉🎉",
    "",
    " ",
    "\n",
    "a",
]


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    """(rust Tokenizer, NativeBPETokenizer) trained on the same data with the
    Qwen2 configuration."""
    from tokenizers import Regex, Tokenizer, decoders, models, normalizers, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE())
    tok.normalizer = normalizers.NFC()
    tok.pre_tokenizer = pre_tokenizers.Sequence([
        pre_tokenizers.Split(Regex(QWEN2_PATTERN), behavior="isolated", invert=False),
        pre_tokenizers.ByteLevel(add_prefix_space=False, use_regex=False),
    ])
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=600,
        special_tokens=["<|endoftext|>", "<|im_start|>", "<|im_end|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False,
    )
    tok.train_from_iterator(CORPUS * 4, trainer)
    path = str(tmp_path_factory.mktemp("tok") / "tokenizer.json")
    tok.save(path)
    native = NativeBPETokenizer.from_hf_file(path, eos_token_id=0)
    return tok, native, path


class TestEncodeParity:
    @pytest.mark.parametrize("i", range(len(CORPUS)))
    def test_corpus(self, pair, i):
        rust, native, _ = pair
        text = CORPUS[i]
        assert native.encode(text) == rust.encode(text).ids, text

    @pytest.mark.parametrize("i", range(len(TRICKY)))
    def test_tricky(self, pair, i):
        rust, native, _ = pair
        text = TRICKY[i]
        assert native.encode(text) == rust.encode(text).ids, repr(text)

    def test_random_ascii_fuzz(self, pair):
        import random

        rust, native, _ = pair
        rng = random.Random(0)
        alphabet = "ab c12.\n'(−αβ数"
        for _ in range(200):
            text = "".join(rng.choice(alphabet) for _ in range(rng.randrange(1, 40)))
            assert native.encode(text) == rust.encode(text).ids, repr(text)


class TestDecodeParity:
    def test_roundtrip(self, pair):
        rust, native, _ = pair
        for text in CORPUS + TRICKY:
            ids = rust.encode(text).ids
            assert native.decode(ids, skip_special_tokens=False) == rust.decode(
                ids, skip_special_tokens=False
            ), repr(text)

    def test_skip_specials(self, pair):
        rust, native, _ = pair
        text = "<|im_start|>user\nhi<|im_end|>"
        ids = rust.encode(text).ids
        assert native.decode(ids, skip_special_tokens=True) == rust.decode(
            ids, skip_special_tokens=True
        )


class TestDetection:
    def test_qwen2_pattern_detected(self, pair):
        _, _, path = pair
        with open(path, encoding="utf-8") as f:
            tj = json.load(f)
        assert _detect_pretok_kind(tj) == 1

    def test_gpt2_pattern_detected(self):
        tj = {"pre_tokenizer": {"type": "ByteLevel", "use_regex": True,
                                "pattern": {"Regex": r"'s|'t| ?\p{L}+| ?\p{N}+"}}}
        assert _detect_pretok_kind(tj) == 0

    def test_patternless_bytelevel_is_gpt2(self):
        """Real GPT-2-family files carry ByteLevel with NO Regex key — its
        built-in split IS the GPT-2 pattern (use_regex defaults true)."""
        assert _detect_pretok_kind(
            {"pre_tokenizer": {"type": "ByteLevel", "add_prefix_space": False}}
        ) == 0
        assert _detect_pretok_kind(
            {"pre_tokenizer": {"type": "ByteLevel", "use_regex": True}}
        ) == 0
        # regex-less ByteLevel (always paired with an explicit Split in
        # Qwen2-style files) → modern default
        assert _detect_pretok_kind(
            {"pre_tokenizer": {"type": "ByteLevel", "use_regex": False}}
        ) == 1
        assert _detect_pretok_kind({}) == 1

    def test_missing_eos_raises(self, pair, tmp_path):
        _, _, path = pair
        with open(path, encoding="utf-8") as f:
            tj = json.load(f)
        tj["added_tokens"] = [
            t for t in tj.get("added_tokens", []) if t["content"] == "<|endoftext|>"
        ] and []
        bad = tmp_path / "tokenizer.json"
        bad.write_text(json.dumps(tj))
        with pytest.raises(ValueError, match="EOS"):
            NativeBPETokenizer.from_hf_file(str(bad))
