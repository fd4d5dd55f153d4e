"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it served is drawn
from the seed: the one with the most served tokens, then others in a
seeded order until ``sample_tokens`` served tokens or ``max_requests``
requests are in it.  The plain reference runs once over each sampled
prompt followed by its served tokens (teacher forcing).  For every
served token the gap is the reference's best logit at that position
minus the reference's logit of the served token: 0 where the program
chose the reference's argmax, and small where bfloat16 rounding flipped
a near tie.  The widest gap over the sample is compared with the cell's
limit (``bench/cells/<workload>.json``), and so is the share of served
tokens that are not the reference's argmax (``flip_share``): rounding
flips a few near ties, a lower precision many more.  Where a cell records the
logits its served tokens were chosen from (``record_logits``: cells
whose windows serve too few tokens for a gap to separate sound runs
from the control), the largest absolute difference between those
logits and the reference's, over the whole vocabulary, is compared too
(``logit_err``): at each position the largest absolute difference over
the vocabulary, and the median of that over the positions.  The median
and not the largest: a position where bfloat16 rounding flips a near tie
of the router sends the token to another expert, and there the logits
move by up to the whole logit scale in sound runs too.

The control reads the same positions with the reference computed in
float8 (``precision="fp8"``): the gap of the token that it ranks first,
and its logits' largest difference from the float32 reference.
"""
from __future__ import annotations

import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_limits(workload: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "cells", f"{workload}.json")) as fh:
        return json.load(fh)


def reference(m: dict):
    return importlib.import_module(f"bench.reference.{m['reference']}")


def sample(served, seed: int, sample_tokens: int, max_requests: int):
    """Indices into ``served`` (a list of (prompt, output, logits)): the
    one with the most served tokens first, then a seeded order."""
    if not served:
        return []
    n_out = np.array([len(s[1]) for s in served])
    first = int(np.argmax(n_out))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 7])
    order = [first] + [int(i) for i in rng.permutation(len(served))
                       if i != first]
    out, tokens = [], 0
    for i in order:
        if tokens >= sample_tokens or len(out) >= max_requests:
            break
        out.append(i)
        tokens += int(n_out[i])
    return out


def teacher_forced(served, picks):
    """Reference inputs for the picked (prompt, output, ...) entries: each
    sequence is the prompt and every served token but the last, and its
    rows are the positions whose logits chose the served tokens."""
    seqs, rows, targets = [], [], []
    for i in picks:
        p, o = served[i][:2]
        o = np.asarray(o, np.int32)
        seqs.append(np.concatenate([np.asarray(p, np.int32), o[:-1]]))
        rows.append(np.arange(len(p) - 1, len(p) - 1 + len(o)))
        targets.append(o)
    return seqs, rows, targets


def gaps(ref_rows, tokens):
    """Per position: best reference logit minus the reference logit of
    ``tokens`` (the token chosen there)."""
    return np.concatenate([r.max(axis=1) - r[np.arange(len(t)), t]
                           for r, t in zip(ref_rows, tokens)])


def logit_errs(rows, logits):
    """Per position: the largest |logit - reference logit| over the
    vocabulary (the program's vocabulary padding cut off)."""
    return np.concatenate([np.abs(np.stack(p)[:, :r.shape[1]] - r).max(1)
                           for r, p in zip(rows, logits)])


def compare(m: dict, seed: int, served, limits: dict, control=False):
    """Run the comparison.  Returns the readings (``widest_gap``, and
    ``logit_err`` where the served entries carry logits) with counts,
    and with ``control=True`` the float8 control's readings at the same
    positions (``control_widest_gap``, ``control_logit_err``) and the
    widest gap if every served token were altered to the next id
    (``altered_widest_gap``)."""
    picks = sample(served, seed, limits["sample_tokens"],
                   limits["max_requests"])
    out = {"requests": len(picks), "tokens": 0, "finite": True}
    if not picks:
        return out
    seqs, rows, targets = teacher_forced(served, picks)
    ref = reference(m).logits_at(m, seed, seqs, rows, "f32")
    out["finite"] = bool(all(np.isfinite(r).all() for r in ref))
    g = gaps(ref, targets)
    out.update(tokens=int(g.size), widest_gap=float(g.max()),
               median_gap=float(np.median(g)),
               flipped=int(np.count_nonzero(g > 0)),
               flip_share=float(np.count_nonzero(g > 0) / g.size))
    logits = [served[i][2] for i in picks]
    if all(x for x in logits):
        e = logit_errs(ref, logits)
        out.update(logit_err=float(np.median(e)), logit_err_max=float(e.max()))
    if control:
        # the altered-token fault, planted in the reference's place: every
        # served token replaced by the next id where it is produced
        ga = gaps(ref, [(t + 1) % r.shape[1] for r, t in zip(ref, targets)])
        low = reference(m).logits_at(m, seed, seqs, rows, "fp8")
        gc = gaps(ref, [r.argmax(axis=1) for r in low])
        out.update(altered_widest_gap=float(ga.max()),
                   control_widest_gap=float(gc.max()),
                   control_flipped=int(np.count_nonzero(gc > 0)),
                   control_flip_share=float(np.count_nonzero(gc > 0)
                                            / gc.size),
                   control_logit_err=float(np.median(logit_errs(ref, low))))
    return out


def verdict(res: dict, limits: dict):
    """(correct, compared): every number the cell compares against its
    limit; a number that could not be read fails."""
    compared = {k: {"value": res.get(k), "limit": v}
                for k, v in limits["compare"].items()}
    ok = res.get("tokens", 0) > 0 and res.get("finite", False) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    return ok, compared
