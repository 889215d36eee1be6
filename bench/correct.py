"""Whether what the timed window served is right.

After the window has closed and the server is gone, a sample of the
requests it finished, drawn from the seed and holding the longest one,
is scored by the configuration's plain reference: one float32 forward
pass over each prompt with its served tokens.  The number compared is
the widest gap by which a served token's float32 logit lies below the
float32 best at its position (``max_logit_gap``).  Greedy bf16 serving
picks the best token up to bf16 rounding, so a sound run reads a small
gap; a wrong token, a stale cache or a broken layer reads a large one.

Besides the gap, no request the run sent may have failed: raised,
ended short of the tokens it asked for, or produced no first token by
the end of the grace period; and every token streamed lies inside the
vocabulary.  (Streams still running when the run stops waiting are
cut, not failed: only finished requests are sampled.)

The control (``control_gaps``) is the same reference in fp8: at every
position of the same sequences it reads the float32 gap of the token
the fp8 forward puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from . import spec as spec_mod
from .stats import Record


def sample(records: Sequence[Record], n: int, seed: int) -> List[Record]:
    """``n`` finished requests drawn from the seed, always with the
    longest (prompt plus served tokens)."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens),
                                       -r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


class Scorer:
    """The reference for one cell, compiled once for the cell's longest
    sequence and output."""

    def __init__(self, config: Dict[str, Any], max_len: int, max_new: int,
                 modes: Tuple[str, ...] = ("f32",)):
        ref = spec_mod.load_reference(config["reference"])
        self.ref = ref
        self.length = ref.pad_to(max_len)
        self.rows = max_new
        self.fns = {m: ref.build(config, self.length, self.rows, m)
                    for m in modes}

    def logits(self, mode, params, prompt, served):
        seq, at = self.ref.sequence(prompt, served, self.length)
        idx = np.zeros(self.rows, np.int32)
        idx[:len(at)] = at
        out = np.asarray(self.fns[mode](params, seq, idx))
        return out[:len(at)]


def served_gaps(scorer: Scorer, params, prompt, served) -> np.ndarray:
    """Per served token: float32 best logit minus the served token's."""
    lg = scorer.logits("f32", params, prompt, served)
    return lg.max(-1) - lg[np.arange(len(served)), served]


def control_gaps(scorer: Scorer, params, prompt, served) -> np.ndarray:
    """Per position: float32 best minus the float32 logit of the token
    the fp8 reference puts first."""
    hi = scorer.logits("f32", params, prompt, served)
    lo = scorer.logits("fp8", params, prompt, served)
    pick = lo.argmax(-1)
    return hi.max(-1) - hi[np.arange(len(served)), pick]


def check(records: Sequence[Record], picked: Sequence[Record],
          gaps: Sequence[np.ndarray], vocab: int,
          limits: Dict[str, Any]) -> Tuple[bool, Dict[str, Dict]]:
    """Hold the run to its limits.  Returns (correct, checks), ``checks``
    mapping each number compared to its value and limit."""
    failed = sum(1 for r in records if r.failed)
    bad_ids = sum(1 for r in records for t in r.tokens
                  if not 0 <= t < vocab)
    gap = max((float(g.max()) for g in gaps if len(g)), default=None)
    finite = all(np.isfinite(g).all() for g in gaps)
    compared = int(sum(len(g) for g in gaps))
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "tokens_outside_vocab": {"value": bad_ids, "limit": 0},
        "tokens_compared": {"value": compared,
                            "min": int(limits["min_tokens_compared"])},
        "max_logit_gap": {"value": gap,
                          "limit": float(limits["max_logit_gap"])},
    }
    ok = (failed == 0 and bad_ids == 0 and finite and gap is not None
          and compared >= checks["tokens_compared"]["min"]
          and gap <= checks["max_logit_gap"]["limit"])
    return ok, checks
