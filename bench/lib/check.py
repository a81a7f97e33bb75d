"""The comparison that decides `correct`.

After the window closes and the program's state is freed, a sample of the
served sessions drawn from the seed, the longest among them, is run
through the plain reference once each: the prompt followed by every token
the front streamed.  At each served token the reference's best logit is
compared with the reference's logit of the served token (greedy decoding
serves the argmax of the program's own logits):

    gap_max   the widest gap over every compared token
    gap_mean  the mean gap over every compared token

Each is held to the cell's limit (`bench/limits/<workload>.json`).  Every
compared session has to reach past its slot's first fold (recompress):
`sessions_before_first_fold` counts those whose last served token is
earlier than the first token decoded from a folded cache, and is held to
0, so the comparison always covers the fold's error.  The
control (`control=True`) puts the reference, computed one precision lower,
in the program's place: at the same positions it reads the gap of the
token the lower precision puts first.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("gap_max", "gap_mean")
ROW_BLOCK = 128


def sample(served, k: int, seed: int):
    """The longest served session plus k - 1 others drawn from the seed."""
    done = [r for r in served if r["tokens"]]
    if not done:
        raise ValueError("no session served a token")
    longest = max(range(len(done)), key=lambda i: (
        len(done[i]["prompt"]) + len(done[i]["tokens"]), -i))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    pick = rng.choice(rest, size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[i] for i in sorted(pick)]


def first_token_after_fold(interval: int) -> int:
    """Index of a request's first token decoded from its folded cache.
    Token 0 comes from prefill; the decode that emits token `interval`
    brings a slot's count of appended tokens to the interval, and the fold
    runs right after it, so token `interval + 1` is the first to read it."""
    return interval + 1


def before_first_fold(chosen, interval: int) -> int:
    """How many chosen sessions end before their first fold is read."""
    first = first_token_after_fold(interval)
    return sum(len(r["tokens"]) - 1 < first for r in chosen)


def readings(ref, w, model: dict, chosen, seq_len: int,
             control: bool = False) -> dict:
    """gap_max / gap_mean over the chosen sessions.  Every sequence is
    right-padded to `seq_len` so one reference program serves them all
    (causal: padding after a position never changes its logits)."""
    gaps = []
    for r in chosen:
        prompt = np.asarray(r["prompt"], np.int32)
        toks = np.asarray(r["tokens"], np.int32)
        seq = np.zeros(seq_len, np.int32)
        body = np.concatenate([prompt, toks[:-1]])
        if len(body) > seq_len:
            raise ValueError(f"{len(body)} tokens exceed the reference's "
                             f"{seq_len}")
        seq[:len(body)] = body
        n = len(toks)
        # rows padded to a multiple of ROW_BLOCK (repeating the last) so a
        # few programs serve every count of served tokens
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        rows = np.pad(rows, (0, -n % ROW_BLOCK), mode="edge")
        lg = np.asarray(ref.logits(w, model, seq, rows, "f32"))[:n]
        if control:
            pick = np.asarray(
                ref.logits(w, model, seq, rows, "fp8"))[:n].argmax(-1)
        else:
            pick = toks
        gaps.append(lg.max(-1) - lg[np.arange(len(toks)), pick])
    g = np.concatenate(gaps)
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "tokens": int(g.size), "sessions": len(chosen)}


def judge(read: dict, limits: dict) -> tuple:
    """(correct, {number: {"value", "limit"}}): every compared number at or
    under its limit."""
    shown = {k: {"value": read[k], "limit": limits[k]}
             for k in NUMBERS if k in limits}
    ok = bool(shown) and all(v["value"] <= v["limit"] for v in shown.values())
    return ok, shown
