"""Differential tests: C++ sentencepiece Unigram core vs the Rust
`tokenizers` implementation (the library the reference tokenizes through).

The sentencepiece half of N7 (SURVEY §2b). Fixtures are built in-process
with the Rust lib (no-egress host: no real Gemma checkpoint), shaped like
Gemma's serialization: Unigram model with ▁-escaped pieces, byte-fallback
pieces for all 256 bytes, Replace(" "→"▁") normalizer, and special tokens.
Exactness contract: C++ ids == Rust ids on every input.
"""

import json

import numpy as np
import pytest

tokenizers = pytest.importorskip("tokenizers")

from tokenizers import Tokenizer  # noqa: E402
from tokenizers.models import Unigram  # noqa: E402

from distrl_llm_tpu.native.build import native_available  # noqa: E402

if not native_available():  # pragma: no cover
    pytest.skip("g++ unavailable", allow_module_level=True)

from distrl_llm_tpu.native.spm import (  # noqa: E402
    NativeSPMTokenizer,
    serialize_hf_unigram,
)


WORDS = [
    "the", "quick", "brown", "fox", "jumps", "over", "lazy", "dog",
    "solve", "equation", "answer", "reason", "math", "prob", "lem",
    "ing", "tion", "er", "est", "un", "re", "s", "ed",
]


def _build_pair(byte_fallback=True, specials=("<pad>", "<eos>", "<bos>")):
    """(rust Tokenizer, C++ NativeSPMTokenizer) over the same vocab."""
    rng = np.random.default_rng(0)
    vocab: list = [("<unk>", 0.0)]
    seen = {"<unk>"}
    for w in WORDS:
        for piece in (w, "▁" + w):
            if piece not in seen:
                seen.add(piece)
                vocab.append((piece, float(-rng.uniform(1.0, 8.0))))
    for ch in "abcdefghijklmnopqrstuvwxyz0123456789.,!?▁":
        if ch not in seen:
            seen.add(ch)
            vocab.append((ch, float(-rng.uniform(8.0, 14.0))))
    if byte_fallback:
        for b in range(256):
            vocab.append((f"<0x{b:02X}>", float(-rng.uniform(10.0, 12.0))))
    base = len(vocab)
    added = [
        {"id": base + i, "content": s, "special": True}
        for i, s in enumerate(specials)
    ]
    for t in added:
        vocab.append((t["content"], 0.0))

    rust = Tokenizer(Unigram(vocab[:base], unk_id=0, byte_fallback=byte_fallback))
    rust.add_special_tokens([t["content"] for t in added])
    # Gemma-style whitespace escaping
    from tokenizers.normalizers import Replace

    rust.normalizer = Replace(" ", "▁")

    tj = {
        "model": {
            "type": "Unigram",
            "unk_id": 0,
            "vocab": [[p, s] for p, s in vocab[:base]],
            "byte_fallback": byte_fallback,
        },
        "added_tokens": added,
        "normalizer": {
            "type": "Replace", "pattern": {"String": " "}, "content": "▁",
        },
    }
    eos = base + specials.index("<eos>") if "<eos>" in specials else 1
    native = NativeSPMTokenizer(
        serialize_hf_unigram(tj),
        eos_token_id=eos,
        normalizer_ops=[("replace", " ", "▁")],
    )
    return rust, native


CASES = [
    "the quick brown fox jumps over the lazy dog",
    "solve the equation",
    "unreasonable problems",
    "  double  spaces  ",
    "reasoning, answers!",
    "MiXeD caSe UNKNOWN",
    "héllo wörld — ünïcode",
    "日本語のテキスト",
    "math. 12345 problems?",
    "",
    " ",
    "a",
    "▁already▁escaped",
    "emoji 🙂 test",
    "tab\tand\nnewline",
]


class TestDifferential:
    def test_fixed_corpus_exact(self):
        rust, native = _build_pair()
        for text in CASES:
            expect = rust.encode(text).ids
            got = native.encode(text)
            assert got == expect, (text, got, expect)

    def test_specials_match_verbatim(self):
        rust, native = _build_pair()
        text = "the<eos>quick <bos> fox"
        assert native.encode(text) == rust.encode(text).ids

    def test_no_byte_fallback_unk_fuses(self):
        rust, native = _build_pair(byte_fallback=False)
        for text in ["héllo", "日本 語", "aé日b"]:
            expect = rust.encode(text).ids
            got = native.encode(text)
            assert got == expect, (text, got, expect)

    def test_llama_style_prepend_exact(self):
        """Llama-2's dummy prefix: Sequence[Prepend(▁), Replace(" "→"▁")] —
        Prepend is unconditional on non-empty text."""
        from tokenizers.normalizers import Prepend, Replace, Sequence

        rust, native = _build_pair()
        rust.normalizer = Sequence([Prepend("▁"), Replace(" ", "▁")])
        native._norm_ops = [("prepend", "▁", ""), ("replace", " ", "▁")]
        for text in CASES + ["▁pre", " lead", "x"]:
            expect = rust.encode(text).ids
            got = native.encode(text)
            assert got == expect, (text, got, expect)

    def test_fuzz_exact(self):
        rust, native = _build_pair()
        rng = np.random.default_rng(7)
        alphabet = list("abcdefghij xyz.,!?é日🙂▁<>0x") + WORDS
        for _ in range(300):
            n = int(rng.integers(0, 24))
            text = "".join(
                str(alphabet[int(k)]) for k in rng.integers(0, len(alphabet), n)
            )
            expect = rust.encode(text).ids
            got = native.encode(text)
            assert got == expect, (text, got, expect)

    def test_decode_roundtrip(self):
        rust, native = _build_pair()
        for text in CASES:
            ids = native.encode(text)
            # rust decode applies no decoder here; compare against the
            # sentencepiece surface convention instead: ▁ → space
            out = native.decode(ids, skip_special_tokens=True)
            # byte-fallback pieces reassemble into the original UTF-8; the
            # ▁↔space mapping is lossy by convention (literal ▁ in the
            # input decodes as a space, as in sentencepiece itself)
            assert out == text.replace("▁", " "), (text, out)

    def test_decode_skips_specials(self):
        _, native = _build_pair()
        ids = native.encode("the<eos>fox")
        with_sp = native.decode(ids, skip_special_tokens=False)
        without = native.decode(ids, skip_special_tokens=True)
        assert "<eos>" in with_sp
        assert "<eos>" not in without


class TestLoadTokenizerDispatch:
    def test_unigram_checkpoint_loads_native_spm(self, tmp_path):
        """load_tokenizer must route Unigram tokenizer.json to the C++ SPM
        core (once a gap: Gemma silently fell back to HF)."""
        _, native = _build_pair()  # builds the serialized fixture pieces
        rng = np.random.default_rng(0)
        vocab = [["<unk>", 0.0], ["▁hi", -1.0], ["hi", -1.5]]
        vocab += [[f"<0x{b:02X}>", -10.0] for b in range(256)]
        base = len(vocab)
        tj = {
            "model": {
                "type": "Unigram", "unk_id": 0, "vocab": vocab,
                "byte_fallback": True,
            },
            "added_tokens": [
                {"id": base, "content": "<pad>", "special": True},
                {"id": base + 1, "content": "<eos>", "special": True},
            ],
            "normalizer": {
                "type": "Replace", "pattern": {"String": " "}, "content": "▁",
            },
        }
        (tmp_path / "tokenizer.json").write_text(json.dumps(tj))
        from distrl_llm_tpu.tokenizer import load_tokenizer

        tok = load_tokenizer(str(tmp_path))
        assert isinstance(tok, NativeSPMTokenizer)
        assert tok.eos_token_id == base + 1
        assert tok.pad_token_id == base
        # no dummy prefix: first word matches "hi", second "▁hi"
        assert tok.encode("hi hi") == [2, 1]

    def test_gemma_normalizer_and_eos_conventions(self):
        """<end_of_turn> joins the EOS set (Gemma chat turns end with it)."""
        vocab = [["<unk>", 0.0], ["▁x", -1.0]]
        base = len(vocab)
        tj = {
            "model": {"type": "Unigram", "unk_id": 0, "vocab": vocab,
                      "byte_fallback": False},
            "added_tokens": [
                {"id": base, "content": "<eos>", "special": True},
                {"id": base + 1, "content": "<end_of_turn>", "special": True},
            ],
            "normalizer": None,
        }
        import json as _json
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            p = f"{d}/tokenizer.json"
            with open(p, "w") as f:
                _json.dump(tj, f)
            tok = NativeSPMTokenizer.from_hf_file(p)
        assert tok.eos_token_id == base
        assert sorted(tok.eos_token_ids) == [base, base + 1]
