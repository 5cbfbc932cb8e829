"""Prompt templating and DictDataset tests (reference: helper.py:3–23)."""

import numpy as np
import pytest

from distrl_llm_tpu.data import R1_PREPROMPT, DictDataset, build_chat_prompt, process_dataset


class FakeTokenizer:
    """Minimal chat-template surface; renders roles/content deterministically."""

    chat_template = None

    def apply_chat_template(
        self, messages, add_generation_prompt=False, tokenize=False, chat_template=None
    ):
        out = "".join(f"<|im_start|>{m['role']}\n{m['content']}<|im_end|>\n" for m in messages)
        if add_generation_prompt:
            out += "<|im_start|>assistant\n"
        return out


class TestBuildChatPrompt:
    def test_system_then_user_with_generation_prompt(self):
        prompt = build_chat_prompt(FakeTokenizer(), "What is 2+2?", R1_PREPROMPT, "")
        assert prompt.startswith("<|im_start|>system\n" + R1_PREPROMPT)
        # reference joins problem + ' ' + postprompt (helper.py:14)
        assert "What is 2+2? <|im_end|>" in prompt
        assert prompt.endswith("<|im_start|>assistant\n")

    def test_preprompt_is_verbatim_r1(self):
        assert "<think> reasoning process here </think>" in R1_PREPROMPT
        assert "<answer> answer here </answer>" in R1_PREPROMPT


class TestProcessDataset:
    def test_dict_input(self):
        data = {"problem": ["1+1?", "2+2?"], "solution": ["2", "4"]}
        out = process_dataset(FakeTokenizer(), data, R1_PREPROMPT)
        assert len(out["problem"]) == 2
        assert all(p.endswith("<|im_start|>assistant\n") for p in out["problem"])
        assert out["solution"] == ["2", "4"]  # untouched columns pass through


class TestDictDataset:
    def test_len_and_iter(self):
        ds = DictDataset({"problem": list("abcdefg"), "solution": list("1234567")})
        assert len(ds) == 7
        batches = list(ds.iter(3))
        assert [len(b["problem"]) for b in batches] == [3, 3, 1]
        assert batches[0]["problem"] == ["a", "b", "c"]

    def test_shuffle_is_permutation(self):
        ds = DictDataset({"x": list(range(100)), "y": list(range(100))}, seed=0)
        sh = ds.shuffle()
        assert sorted(sh["x"]) == list(range(100))
        assert sh["x"] != list(range(100))
        # columns stay aligned
        assert sh["x"] == sh["y"]

    def test_ragged_raises(self):
        with pytest.raises(ValueError, match="ragged"):
            DictDataset({"a": [1], "b": [1, 2]})

    def test_wrap_passthrough(self):
        ds = DictDataset({"a": [1]})
        assert DictDataset.wrap(ds) is ds
        assert isinstance(DictDataset.wrap({"a": [1]}), DictDataset)


class TestGsm8k:
    """GSM8K prep (reference recipe 3's dataset): '#### N' gold-answer
    extraction feeding the same exact-match reward contract."""

    @pytest.mark.parametrize("raw,want", [
        ("Natalia sold clips.\n#### 72", "72"),
        ("Step one.\nStep two.\n#### 1,234", "1234"),
        ("#### $18", "18"),
        ("   #### -5   ", "-5"),
        ("no marker at all", "no marker at all"),
    ])
    def test_extract_solution(self, raw, want):
        from distrl_llm_tpu.data import extract_gsm8k_solution

        assert extract_gsm8k_solution(raw) == want

    def test_reward_contract_on_extracted_solution(self):
        from distrl_llm_tpu.data import extract_gsm8k_solution
        from distrl_llm_tpu.rewards import reward_function

        sol = extract_gsm8k_solution("reasoning...\n#### 42")
        r = reward_function(["<answer>42</answer>", "<answer>41</answer>"], [sol, sol])
        assert r[0, 1] == 1.0 and r[1, 1] == 0.0

    def test_prepare_dataset_dispatch(self, monkeypatch):
        """Dispatch by dataset id: gsm8k ids route to the GSM8K loader,
        everything else to the MATH-500 loader (hub access stubbed out)."""
        import distrl_llm_tpu.data as data

        calls = []
        monkeypatch.setattr(
            data, "prepare_gsm8k", lambda *a, **k: calls.append("gsm8k")
        )
        monkeypatch.setattr(
            data, "prepare_math500", lambda *a, **k: calls.append("math500")
        )
        data.prepare_dataset("openai/gsm8k", None)
        data.prepare_dataset("HuggingFaceH4/MATH-500", None)
        assert calls == ["gsm8k", "math500"]
