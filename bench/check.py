"""Whether the window served what the plain references say.

Every task must have been served in full: departed, with ``1 + decode``
tokens in each of its rows, all inside the vocabulary.  Then a sample of
finished tasks drawn from the seed (``sample``: per model the longest
task and others) is run once through the model's float32 reference over
its prompt and its served tokens.  At each served position the gap is
the reference's best logit minus the served token's; per model the
numbers are the widest gap and the mean gap, each compared with the
limit the configuration file gives it.  The control (``low``) puts the
reference computed in fp8 in the program's place and reads the float32
gap of the token it ranks first.
"""
from __future__ import annotations

import importlib
import random
from typing import Any, Dict, List, Tuple

import numpy as np

PROMPT_CAP = 4096   # prompts are slices of one fixed-length stream


def prompt_tokens(prompt_seed: int, rows: int, length: int, vocab: int):
    """A task's prompt: the first ``length`` tokens of the stream
    ``randint(PRNGKey(7919 + prompt_seed), (rows, 4096))``, the recipe
    the traffic's prompt seeds stand for."""
    import jax
    toks = jax.random.randint(jax.random.PRNGKey(7919 + prompt_seed),
                              (rows, PROMPT_CAP), 0, vocab)
    return np.asarray(toks, np.int32)[:, :length]


def served_failures(models: Dict[str, Any], tasks: List[Dict[str, Any]],
                    records: List[Dict[str, Any]], rows: int) -> List[str]:
    bad = []
    for j, (t, r) in enumerate(zip(tasks, records)):
        toks = np.asarray(r["tokens"])
        vocab = vocab_size(models[t["model"]])
        if not r["departed"] or toks.shape != (rows, 1 + t["decode"]) \
                or toks.min() < 0 or toks.max() >= vocab:
            bad.append(f"task {j} ({t['model']}, prompt {t['prompt']}): "
                       f"departed={r['departed']}, tokens {toks.shape}")
    return bad


def vocab_size(entry: Dict[str, Any]) -> int:
    return entry["config"]["vocab_size"]


def sample(tasks: List[Dict[str, Any]], seed: int,
           min_tokens: int = 512) -> List[int]:
    """Per model: its longest task, then others drawn from the seed until
    the sample holds at least two tasks and ``min_tokens`` served tokens
    in each row."""
    rng = random.Random(f"{seed}/sample")
    picked: List[int] = []
    for model in sorted({t["model"] for t in tasks}):
        idx = [j for j, t in enumerate(tasks) if t["model"] == model]
        longest = max(idx, key=lambda j: (tasks[j]["prompt"]
                                          + tasks[j]["decode"], -j))
        rest = [j for j in idx if j != longest]
        rng.shuffle(rest)
        mine, served = [longest], 1 + tasks[longest]["decode"]
        for j in rest:
            if len(mine) >= 2 and served >= min_tokens:
                break
            mine.append(j)
            served += 1 + tasks[j]["decode"]
        picked += mine
    return picked


def task_gaps(entry: Dict[str, Any], task: Dict[str, Any],
              tokens: np.ndarray, low: bool = False) -> np.ndarray:
    """Gaps [rows, 1 + decode] of one task's served tokens (or, with
    ``low``, of the tokens the fp8 control ranks first at the same
    positions)."""
    import jax.numpy as jnp
    from reference.common import gaps
    ref = importlib.import_module(f"reference.{entry['family']}")
    rows, P = tokens.shape[0], task["prompt"]
    prompt = prompt_tokens(task["prompt_seed"], rows, P, vocab_size(entry))
    seq = jnp.asarray(np.concatenate([prompt, tokens[:, :-1]], axis=1))
    z = ref.logits(entry["config"], task["param_seed"], seq, start=P - 1)
    if low:
        served = jnp.argmax(ref.logits(entry["config"], task["param_seed"],
                                       seq, start=P - 1, low=True), axis=-1)
    else:
        served = jnp.asarray(tokens)
    return np.asarray(gaps(z, served))


def gap_numbers(models: Dict[str, Any], tasks: List[Dict[str, Any]],
                records: List[Dict[str, Any]], picked: List[int],
                low: bool = False) -> Dict[str, float]:
    """Per model, over every sampled position: the widest gap
    (``<model>.gap_max``) and the mean gap (``<model>.gap_mean``)."""
    per: Dict[str, List[np.ndarray]] = {}
    for j in picked:
        t = tasks[j]
        per.setdefault(t["model"], []).append(task_gaps(
            models[t["model"]], t, np.asarray(records[j]["tokens"]), low))
    out: Dict[str, float] = {}
    for model, gs in sorted(per.items()):
        flat = np.concatenate([g.ravel() for g in gs])
        out[f"{model}.gap_max"] = float(flat.max())
        out[f"{model}.gap_mean"] = float(flat.mean())
    return out


def judge(limits: Dict[str, float], numbers: Dict[str, float],
          failures: List[str]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each compared number beside its limit: every
    number the configuration gives a limit for, and the unserved tasks
    (limit 0).  A number that could not be read (null) fails."""
    checks = {"unserved_tasks": {"value": len(failures), "limit": 0}}
    for name, limit in sorted(limits.items()):
        checks[name] = {"value": numbers.get(name), "limit": limit}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
