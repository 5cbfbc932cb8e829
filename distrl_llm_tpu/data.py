"""Dataset preparation and R1-style prompting.

Parity with the reference's helper.py:3–23 and train_distributed.py:38–48:
MATH-500 "test" split, answer→solution rename, 90/10 split, system+user chat
template with ``add_generation_prompt=True``. Works with any HF tokenizer that
carries a chat template; falls back to a plain template for test tokenizers.
"""

from __future__ import annotations

from typing import Any, Iterator, Mapping, Sequence

import numpy as np

# Reference system prompt, verbatim contract (helper.py:3–9).
R1_PREPROMPT = (
    "A conversation between User and Assistant. The user asks a question, and the Assistant solves it.\n"
    "The assistant first thinks about the reasoning process and then provides the user with the answer.\n"
    "The response must follow this format:\n"
    "<think> reasoning process here </think>\n"
    "<answer> answer here </answer>\n"
)

_FALLBACK_TEMPLATE = (
    "{% for message in messages %}"
    "<|im_start|>{{ message['role'] }}\n{{ message['content'] }}<|im_end|>\n"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|im_start|>assistant\n{% endif %}"
)


def build_chat_prompt(tokenizer, problem: str, preprompt: str = "", postprompt: str = "") -> str:
    """One problem → chat-templated prompt string (helper.py:12–21: system =
    preprompt, user = problem + ' ' + postprompt, generation prompt appended)."""
    messages = [
        {"role": "system", "content": preprompt},
        {"role": "user", "content": problem + " " + postprompt},
    ]
    kwargs = {}
    # Template-less tokenizers (tiny test tokenizers) get a ChatML-style
    # fallback passed per-call — the tokenizer object is never mutated.
    if getattr(tokenizer, "chat_template", None) is None:
        kwargs["chat_template"] = _FALLBACK_TEMPLATE
    return tokenizer.apply_chat_template(
        messages, add_generation_prompt=True, tokenize=False, **kwargs
    )


def process_dataset(tokenizer, dataset, preprompt: str = "", postprompt: str = ""):
    """Map the ``problem`` column through the chat template (helper.py:11–23).

    Accepts either an HF ``datasets.Dataset`` (uses .map) or a plain
    dict-of-lists (returns a new dict) so tests need no datasets dependency.
    """

    def _map(examples: Mapping[str, Sequence[str]]) -> dict[str, list[str]]:
        return {
            "problem": [
                build_chat_prompt(tokenizer, p, preprompt, postprompt)
                for p in examples["problem"]
            ]
        }

    if hasattr(dataset, "map"):
        return dataset.map(_map, batched=True)
    out = dict(dataset)
    out.update(_map(dataset))
    return out


def prepare_math500(dataset_name: str, tokenizer, test_size: float = 0.1, seed: int | None = None):
    """Load + split + template MATH-500 the way the reference CLI does
    (train_distributed.py:38–48): 'test' split only, answer→solution rename,
    train_test_split(0.1), chat templating on both splits."""
    from datasets import load_dataset  # deferred: heavy import

    raw = load_dataset(dataset_name)["test"]
    raw = raw.map(lambda x: {"solution": x["answer"]})
    raw = raw.remove_columns(["answer"])
    split = raw.train_test_split(test_size=test_size, seed=seed)
    train = process_dataset(tokenizer, split["train"], R1_PREPROMPT, "")
    test = process_dataset(tokenizer, split["test"], R1_PREPROMPT, "")
    return train, test


def extract_gsm8k_solution(answer: str) -> str:
    """GSM8K gold answers end with ``#### <number>`` — the graded solution is
    that number with thousands separators stripped (the community-standard
    extraction; the reward's exact-match contract then works unchanged)."""
    tail = answer.rsplit("####", 1)[-1] if "####" in answer else answer
    return tail.strip().replace(",", "").replace("$", "")


def prepare_gsm8k(dataset_name: str, tokenizer, test_size: float = 0.1,
                  seed: int | None = None):
    """Load + template GSM8K (reference recipe 3's dataset). Unlike MATH-500
    (a single 'test' split the reference carves 90/10,
    train_distributed.py:44), GSM8K ships dedicated splits — training on its
    official 1,319-row test set would contaminate every published-accuracy
    comparison, so RL trains on the 7,473-row TRAIN split and evaluates on
    the untouched test split (``test_size`` is unused here; kept for the
    dispatcher's uniform signature)."""
    from datasets import load_dataset  # deferred: heavy import

    raw = load_dataset(dataset_name, "main")

    def remap(ds):
        ds = ds.map(
            lambda x: {
                "problem": x["question"],
                "solution": extract_gsm8k_solution(x["answer"]),
            }
        )
        return ds.remove_columns(
            [c for c in ("question", "answer") if c in ds.column_names]
        )

    train = process_dataset(tokenizer, remap(raw["train"]), R1_PREPROMPT, "")
    test = process_dataset(tokenizer, remap(raw["test"]), R1_PREPROMPT, "")
    return train, test


def prepare_dataset(dataset_name: str, tokenizer, test_size: float = 0.1,
                    seed: int | None = None):
    """Dispatch on the dataset id: GSM8K-style (question/#### answer) or
    MATH-500-style (problem/answer) preparation."""
    if "gsm8k" in dataset_name.lower():
        return prepare_gsm8k(dataset_name, tokenizer, test_size, seed)
    return prepare_math500(dataset_name, tokenizer, test_size, seed)


class DictDataset:
    """Minimal dict-of-lists dataset with the iteration surface the Trainer
    uses (``shuffle()`` / ``iter(batch_size)`` — distributed_trainer.py:245–246).
    Lets the trainer run on plain Python data (tests, offline hosts) and makes
    HF datasets optional rather than load-bearing."""

    def __init__(self, data: Mapping[str, Sequence[Any]], seed: int | None = None):
        lengths = {k: len(v) for k, v in data.items()}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"ragged columns: {lengths}")
        self.data = {k: list(v) for k, v in data.items()}
        self._rng = np.random.default_rng(seed)

    def __len__(self) -> int:
        return len(next(iter(self.data.values()), []))

    def __getitem__(self, key: str) -> list[Any]:
        return self.data[key]

    def shuffle(self, seed: int | None = None) -> "DictDataset":
        """Seedable like HF ``Dataset.shuffle(seed=...)`` — the trainer seeds
        each episode's shuffle deterministically so a mid-episode resume can
        re-derive the same batch order and skip what was already trained."""
        rng = np.random.default_rng(seed) if seed is not None else self._rng
        perm = rng.permutation(len(self))
        shuffled = {k: [v[i] for i in perm] for k, v in self.data.items()}
        out = DictDataset(shuffled)
        out._rng = self._rng
        return out

    def iter(self, batch_size: int) -> Iterator[dict[str, list[Any]]]:
        for start in range(0, len(self), batch_size):
            yield {k: v[start : start + batch_size] for k, v in self.data.items()}

    @staticmethod
    def wrap(dataset) -> "DictDataset | Any":
        """Pass HF datasets through untouched; wrap mappings."""
        if hasattr(dataset, "iter") and hasattr(dataset, "shuffle"):
            return dataset
        return DictDataset(dataset)
