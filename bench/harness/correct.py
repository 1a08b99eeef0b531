"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests the window finished
is drawn from the seed, with the longest among them. The plain reference
runs once over each prompt with its served tokens fed back, and each served
token is judged by how far its reference logit lies below the reference's
best logit at that position (0 where the served token is the reference's
argmax). Two numbers are compared, each against its own limit:

* ``logit_gap``, the widest such gap over the sample: one token sent far
  astray, as a wrong expert, a lost cache or an altered token does;
* ``mean_gap_ratio``, the mean gap over every served token of the sample
  over the mean gap of the tokens that the reference computed in bfloat16
  (the precision the configuration states) puts first at the same
  positions: many tokens nudged, as a lower precision anywhere in the model
  does. The mean gap alone swings tenfold from seed to seed with how many
  near ties the sample holds; the bfloat16 reference meets the same near
  ties, so the ratio does not.

Greedy decoding makes this valid: every served token was an argmax of the
program's logits. A control (``control_gap``) reads, at the same positions,
the gaps of the tokens that a lower-precision variant of the reference puts
first, and is judged by the same limits (``judge``).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Sequence

import numpy as np

LIMITS_DIR = Path(__file__).resolve().parents[1] / "limits"


def load_limits(workload: str, directory: Path = LIMITS_DIR) -> dict:
    path = directory / f"{workload}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no limits for {workload!r} ({path})")
    return json.loads(path.read_text())


def draw_sample(records: Sequence, n: int, seed: int) -> List:
    """Up to ``n`` finished requests: the one with the most served tokens
    (ties: the longer prompt), then others drawn from the seed."""
    done = [r for r in records if r.finished and r.result_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.result_tokens), r.prompt_len,
                                       -r.draw.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 5])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


@dataclasses.dataclass
class Batch:
    tokens: np.ndarray      # (B, T) prompt + served tokens fed back
    n_prompt: np.ndarray    # (B,)
    n_total: np.ndarray     # (B,)
    rows_pos: np.ndarray    # (B, N) position predicting served token j
    served: np.ndarray      # (B, N)
    mask: np.ndarray        # (B, N) bool: a real served token


def build_batch(pairs: Sequence, rows: int, length: int, served: int
                ) -> Batch:
    """``pairs`` of (prompt, served tokens), padded to ``rows`` sequences
    (a copy of the first, masked out) of ``length`` positions and
    ``served`` served tokens: fixed shapes, so the reference's programs
    come from the compilation cache."""
    if not pairs:
        raise ValueError("nothing to compare")
    real = len(pairs)
    pairs = list(pairs) + [pairs[0]] * (rows - real)
    n_max = served
    if max(len(s) for _, s in pairs) > n_max:
        raise ValueError(f"more than {n_max} served tokens")
    b = len(pairs)
    tokens = np.zeros((b, length), np.int32)
    n_prompt = np.zeros(b, np.int32)
    n_total = np.zeros(b, np.int32)
    rows_pos = np.zeros((b, n_max), np.int32)
    out_tok = np.zeros((b, n_max), np.int32)
    mask = np.zeros((b, n_max), bool)
    for i, (prompt, out) in enumerate(pairs):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} > {length}")
        tokens[i, :len(seq)] = seq
        p, n = len(prompt), len(out)
        n_prompt[i], n_total[i] = p, p + n - 1
        rows_pos[i, :n] = p - 1 + np.arange(n)
        rows_pos[i, n:] = p - 1
        out_tok[i, :n] = out
        mask[i, :n] = i < real
    return Batch(tokens, n_prompt, n_total, rows_pos, out_tok, mask)


def _gaps(ref_logits, tokens, batch: Batch, yardstick=None) -> dict:
    """How far the reference's logit for ``tokens`` (B, N) lies below its
    best, over the batch's served positions: widest, mean, and how many
    are not the reference's first choice; with ``yardstick`` (the bfloat16
    reference's mean gap at the same positions), the mean over it."""
    import jax
    import jax.numpy as jnp

    lg = jnp.asarray(ref_logits)
    got = jnp.take_along_axis(lg, jnp.asarray(tokens)[..., None],
                              axis=-1)[..., 0]
    gap = np.asarray(lg.max(-1) - got)[batch.mask]
    top2 = np.asarray(jax.lax.top_k(lg, 2)[0])[batch.mask]
    mean = float(gap.mean())
    ratio = None
    if yardstick is not None and (yardstick > 0 or mean == 0):
        ratio = mean / yardstick if yardstick > 0 else 0.0
    return {"gap": float(gap.max()), "mean_gap": mean,
            "mean_gap_ratio": ratio,
            "argmax_differs": int((gap > 0).sum()), "served": int(gap.size),
            # per served position, for calibration: this gap, and the
            # reference's margin between its two best logits
            "gaps": gap.round(6).tolist(),
            "margins": (top2[:, 0] - top2[:, 1]).round(6).tolist()}


def served_gap(ref_logits, batch: Batch, yardstick=None) -> dict:
    """The gaps of the served tokens."""
    return _gaps(ref_logits, batch.served, batch, yardstick)


def control_gap(ref_logits, ctl_logits, batch: Batch, yardstick=None
                ) -> dict:
    """The gaps of the tokens the control puts first."""
    import jax.numpy as jnp

    return _gaps(ref_logits, jnp.argmax(jnp.asarray(ctl_logits), -1), batch,
                 yardstick)


# the numbers compared against ``bench/limits/<cell>.json``, and the
# reading (``served_gap`` / ``control_gap``) each is taken from
COMPARED = {"logit_gap": "gap", "mean_gap_ratio": "mean_gap_ratio"}


def gap_checks(reading, limits: dict) -> dict:
    """``{number: {"value", "limit"}}`` for a reading, or for none."""
    return {k: {"value": None if reading is None else reading[r],
                "limit": limits[k]["limit"]} for k, r in COMPARED.items()}


def judge(checks: dict) -> bool:
    """Correct: every number compared is read and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
