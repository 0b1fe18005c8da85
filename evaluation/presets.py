"""Benchmark presets for the offline eval harness.

Role counterpart of the reference's evaluation/{data_loader,examples,
utils,parser}.py (prompt templates keyed by model family at
evaluation/utils.py:79-175, per-dataset few-shot demos at examples.py,
per-dataset question/ground-truth field parsing at parser.py:578-720):
given a benchmark NAME and a jsonl file, this module knows which fields
hold the question and the ground-truth answer, which prompt format the
model family expects, and how many worked examples to prepend — so
`math_eval.py benchmark=math500 ...` reproduces the reference's
quality-table methodology without per-run plumbing.

Design differences from the reference (deliberate): templates are small
dataclasses with a `wrap()` method instead of 3-tuples + format-string
special cases; ground truth resolves through ordered field candidates
plus an optional per-benchmark extractor instead of a 150-line if/elif
ladder; few-shot demos are stored once in the template-agnostic
(question, reasoning, answer) form and each template renders them its
own way.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Callable, List, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Prompt templates
# ---------------------------------------------------------------------------

# Doubled braces: template strings pass through str.format exactly once.
BOXED_INSTRUCTION = (
    "Please reason step by step, and put your final answer within "
    "\\boxed{{}}."
)


@dataclasses.dataclass(frozen=True)
class PromptTemplate:
    """Renders (few-shot demos +) a question into a model-ready prompt.

    prefix (optional) emits ONCE at the very start — chat formats put
    the system turn there so demos cannot appear before it; demo_format
    receives (question, full worked answer) pairs joined by demo_sep;
    the final question renders via question_format after the demos."""

    name: str
    question_format: str
    demo_format: str = "{question}\n{answer}"
    demo_sep: str = "\n\n"
    prefix: str = ""

    def wrap(self, question: str,
             shots: Sequence[Tuple[str, str]] = ()) -> str:
        parts = [self.demo_format.format(question=q, answer=a)
                 for q, a in shots]
        parts.append(self.question_format.format(question=question))
        # prefix goes through .format() too: every template string gets
        # exactly one format pass (escaped {{}} in BOXED_INSTRUCTION).
        return self.prefix.format() + self.demo_sep.join(parts)


PROMPT_TEMPLATES = {
    # Bare continuation, no chat markup: base models / quick smoke evals.
    "direct": PromptTemplate(
        name="direct",
        question_format="Question: {question}\nAnswer:",
        demo_format="Question: {question}\nAnswer: {answer}",
    ),
    # Few-shot chain-of-thought in plain text (the classic CoT setup).
    "cot": PromptTemplate(
        name="cot",
        question_format="Question: {question}\nAnswer:",
        demo_format="Question: {question}\nAnswer: {answer}",
        demo_sep="\n\n\n",
    ),
    # Plain instruction + boxed answer, no chat markup.
    "boxed": PromptTemplate(
        name="boxed",
        question_format="{question}\n" + BOXED_INSTRUCTION + "\n",
    ),
    # Qwen2.5-style ChatML with the boxed instruction in the system turn
    # (the format the reference's RL-trained Qwen checkpoints expect).
    "chatml-boxed": PromptTemplate(
        name="chatml-boxed",
        prefix=(
            "<|im_start|>system\n" + BOXED_INSTRUCTION + "<|im_end|>\n"
        ),
        question_format=(
            "<|im_start|>user\n{question}<|im_end|>\n"
            "<|im_start|>assistant\n"
        ),
        demo_format=(
            "<|im_start|>user\n{question}<|im_end|>\n"
            "<|im_start|>assistant\n{answer}<|im_end|>\n"
        ),
        demo_sep="",
    ),
    # Multiple-choice (GPQA/MMLU-style rows whose question text already
    # carries the lettered options): the boxed answer is the LETTER.
    "boxed-choice": PromptTemplate(
        name="boxed-choice",
        question_format=(
            "{question}\nPlease reason step by step, and put the letter "
            "of the correct option within \\boxed{{}}.\n"
        ),
    ),
    # PAL: the model writes a python program whose solution() returns
    # the answer; math_eval answer_mode='python' executes it in the
    # sandboxed subprocess (functioncall/python_answer.py — the role of
    # the reference's evaluation/python_executor.py).
    "pal": PromptTemplate(
        name="pal",
        question_format=(
            "Question: {question}\n"
            "Write a Python program that computes the answer; define "
            "solution() returning it.\n\n```python\n"
        ),
        demo_format="Question: {question}\n{answer}",
        demo_sep="\n---\n",
    ),
    # DeepSeek-R1-Distill family markup with an opened think block.
    "r1-distill": PromptTemplate(
        name="r1-distill",
        question_format=(
            "<｜User｜>{question}\n" + BOXED_INSTRUCTION
            + "<｜Assistant｜><think>\n"
        ),
    ),
}

# ---------------------------------------------------------------------------
# Few-shot worked examples
# ---------------------------------------------------------------------------
# Template-agnostic (question, worked answer) demos, written for this
# repo. GSM8K-grade arithmetic with explicit reasoning and a final
# "The answer is N." that extract_answer picks up; the boxed variant
# swaps the terminal form.

MATH_FEW_SHOT: List[Tuple[str, str]] = [
    (
        "A bookshelf holds 4 rows of 9 books. If 7 books are checked "
        "out, how many books remain on the shelf?",
        "The shelf starts with 4 rows of 9 books, which is 4 * 9 = 36 "
        "books. After 7 are checked out, 36 - 7 = 29 remain. "
        "The answer is 29.",
    ),
    (
        "Tickets cost $12 for adults and $5 for children. What do 2 "
        "adults and 3 children pay in total?",
        "Two adult tickets cost 2 * 12 = 24 dollars. Three child "
        "tickets cost 3 * 5 = 15 dollars. Together that is 24 + 15 = "
        "39 dollars. The answer is 39.",
    ),
    (
        "A cyclist rides 15 km per hour. How far does she ride in 2.5 "
        "hours?",
        "Distance is speed times time: 15 * 2.5 = 37.5 km. "
        "The answer is 37.5.",
    ),
    (
        "A farmer plants 126 seeds in rows of 14. How many rows does "
        "he plant?",
        "Dividing the seeds into rows of 14 gives 126 / 14 = 9 rows. "
        "The answer is 9.",
    ),
]


# PAL-style demos: programs whose solution() returns the answer.
PAL_FEW_SHOT: List[Tuple[str, str]] = [
    (
        "A bookshelf holds 4 rows of 9 books. If 7 books are checked "
        "out, how many books remain on the shelf?",
        "```python\n"
        "def solution():\n"
        "    total = 4 * 9\n"
        "    return total - 7\n"
        "```",
    ),
    (
        "Tickets cost $12 for adults and $5 for children. What do 2 "
        "adults and 3 children pay in total?",
        "```python\n"
        "def solution():\n"
        "    return 2 * 12 + 3 * 5\n"
        "```",
    ),
]


def boxed_shots(shots: Sequence[Tuple[str, str]]) -> List[Tuple[str, str]]:
    """Rewrite 'The answer is N.' demo endings into \\boxed{N} form so
    few-shot demos match the boxed instruction the template gives."""
    out = []
    for q, a in shots:
        if "The answer is " in a:
            head, tail = a.rsplit("The answer is ", 1)
            ans = tail.rstrip().rstrip(".")
            a = head + "The final answer is $\\boxed{" + ans + "}$."
        out.append((q, a))
    return out


# ---------------------------------------------------------------------------
# Benchmark presets
# ---------------------------------------------------------------------------


def _gsm8k_gt(row: dict) -> Optional[str]:
    """GSM8K stores 'reasoning #### answer' in the answer field."""
    ans = row.get("answer")
    if isinstance(ans, str) and "####" in ans:
        return ans.rsplit("####", 1)[1].strip().replace(",", "")
    return ans


@dataclasses.dataclass(frozen=True)
class BenchmarkPreset:
    """Field mapping + eval defaults for a named benchmark jsonl.

    question_keys/answer_keys are ordered candidates (public dumps of
    the same benchmark disagree on field names); answer_fn, when set,
    overrides the key lookup entirely."""

    name: str
    question_keys: Tuple[str, ...] = ("problem", "question", "prompt")
    answer_keys: Tuple[str, ...] = ("answer", "solution", "solutions")
    answer_fn: Optional[Callable[[dict], Optional[str]]] = None
    prompt_type: str = "boxed"
    num_shots: int = 0
    max_new_tokens: int = 4096
    # Multi-sample defaults: small contest sets (AIME: 30 problems) are
    # conventionally reported as avg@k/pass@k over many samples.
    n_samples: int = 1
    temperature: float = 0.6

    def question(self, row: dict) -> str:
        for k in self.question_keys:
            if row.get(k):
                return str(row[k])
        raise KeyError(
            f"benchmark {self.name}: no question field among "
            f"{self.question_keys} in row keys {sorted(row)}"
        )

    def ground_truth(self, row: dict):
        if self.answer_fn is not None:
            val = self.answer_fn(row)
        else:
            val = next(
                (row[k] for k in self.answer_keys
                 if row.get(k) is not None),
                None,
            )
        if val is None:
            # Raise like question() does — on BOTH paths: a silent None
            # would grade every sample wrong against the string 'None'
            # and report a plausible-looking 0.0 accuracy.
            raise KeyError(
                f"benchmark {self.name}: no ground-truth answer found "
                f"in row keys {sorted(row)}"
            )
        return val


BENCHMARKS = {
    "aime24": BenchmarkPreset(
        name="aime24", n_samples=8, max_new_tokens=8192,
    ),
    "aime25": BenchmarkPreset(
        name="aime25", n_samples=8, max_new_tokens=8192,
    ),
    "amc23": BenchmarkPreset(
        name="amc23", n_samples=4, max_new_tokens=4096,
    ),
    "math500": BenchmarkPreset(
        name="math500", max_new_tokens=4096,
    ),
    # GPQA-diamond-style multiple choice: the question field already
    # carries the lettered options; ground truth is the letter.
    "gpqa_diamond": BenchmarkPreset(
        name="gpqa_diamond",
        question_keys=("question", "problem", "prompt"),
        answer_keys=("answer",),
        prompt_type="boxed-choice",
        max_new_tokens=2048,
    ),
    "gsm8k": BenchmarkPreset(
        name="gsm8k",
        answer_fn=_gsm8k_gt,
        prompt_type="cot",
        num_shots=4,
        max_new_tokens=512,
    ),
    # Generic preset for the repo's own prompt/solutions jsonl schema
    # (datasets/math_code_prompt.py), zero-shot boxed. Named "generic",
    # NOT "default": math_eval's no-preset path labels results
    # differently ("none"/"verbatim"), and one label must never cover
    # two prompt behaviors.
    "generic": BenchmarkPreset(name="generic"),
}


def load_benchmark(data_path: str, preset: BenchmarkPreset) -> List[dict]:
    """jsonl -> [{query_id, question, gt}], via the preset's field map."""
    rows = []
    with open(data_path) as f:
        for i, line in enumerate(f):
            if not line.strip():
                continue
            raw = json.loads(line)
            rows.append(
                dict(
                    query_id=str(raw.get("query_id", raw.get("idx", i))),
                    question=preset.question(raw),
                    gt=preset.ground_truth(raw),
                )
            )
    return rows


def build_prompt(question: str, prompt_type: str, num_shots: int) -> str:
    template = PROMPT_TEMPLATES[prompt_type]
    if prompt_type == "pal":
        pool = PAL_FEW_SHOT
    elif prompt_type == "boxed-choice":
        # No letter-answer demos exist; numeric math demos would
        # contradict the boxed-LETTER instruction and bias the model —
        # num_shots > 0 fails loudly via the length check below.
        pool = []
    else:
        pool = MATH_FEW_SHOT
    if num_shots > len(pool):
        # Refuse rather than silently truncate: the result metadata
        # records the REQUESTED shot count, and a published "8-shot"
        # number that actually ran 4-shot would misstate methodology.
        raise ValueError(
            f"num_shots={num_shots} but only {len(pool)} few-shot "
            f"demos are available for {prompt_type!r} "
            f"(evaluation/presets.py)"
        )
    shots = pool[:num_shots]
    if "boxed" in prompt_type or prompt_type == "r1-distill":
        shots = boxed_shots(shots)
    return template.wrap(question, shots)
