"""What decides ``correct``: the tokens the window served, compared with
the plain float32 reference.

After the window closes, a sample of the finished requests is drawn from
the seed, the one with the most served tokens always in it, until it
holds ``SAMPLE_TOKENS`` served tokens from ``SAMPLE_REQUESTS`` requests
or more (or every finished one).  A lower precision's widest errors
fall in a few positions of a few requests, so a sample of one long
request can miss them; the sample spreads over many requests.  The
reference runs once over each prompt followed by its served tokens
(teacher forcing).  Two numbers are compared, each the widest over the
sample:

- ``max_logit_gap``: how far a served token's reference logit lies below
  the reference's best at that position.  Served tokens are greedy, so a
  correct server's gaps come from rounding alone; a wrong cache, kernel
  or LM head puts them far down.  It reads 0 until a token flips.
- ``max_logprob_err``: |the logprob the server gave a token − the
  reference's logprob of it|.  It reads every position, flipped or not.

The control puts the reference computed in int8 (``precision="int8"``)
in the program's place: at each position of the same sequences it takes
the token the int8 forward ranks first, with the int8 forward's logprob
of it, and both numbers are read for those tokens the same way.  The
program and the control go through the same ``checks``.
"""
from __future__ import annotations

import numpy as np

SAMPLE_TOKENS = 256
SAMPLE_REQUESTS = 8
NUMBERS = ("max_logit_gap", "max_logprob_err")


def sample(finished, prompts: dict, seed: int) -> list[tuple]:
    """[(prompt, served tokens, served logprobs)] drawn from the finished
    requests."""
    done = sorted((f for f in finished if len(f.tokens)),
                  key=lambda f: f.uid)
    if not done:
        return []
    longest = max(done, key=lambda f: (len(f.tokens), -f.uid))
    rest = [f for f in done if f is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    picked, n = [longest], len(longest.tokens)
    for j in rng.permutation(len(rest)):
        if n >= SAMPLE_TOKENS and len(picked) >= SAMPLE_REQUESTS:
            break
        picked.append(rest[j])
        n += len(rest[j].tokens)
    return [(prompts[f.uid], np.asarray(f.tokens, np.int32),
             np.asarray(f.logprobs, np.float32)) for f in picked]


def _positions(ref, prompt, served):
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    return ref.logits(seq, len(prompt) - 1)          # [len(served), V]


def _logprob(lg, tok):
    best = lg.max(-1)
    lse = np.log(np.exp(lg - best[:, None]).sum(-1)) + best
    return lg[np.arange(len(tok)), tok] - lse


def _read(lg, tok, lp) -> dict:
    """Per position: the reference's best logit minus that of ``tok``, and
    |``lp`` − the reference's logprob of ``tok``|."""
    return {"max_logit_gap": lg.max(-1) - lg[np.arange(len(tok)), tok],
            "max_logprob_err": np.abs(lp - _logprob(lg, tok))}


def compare(ref, pairs, ctrl=None) -> tuple[dict, dict | None, int]:
    """(program, control, positions): each side's numbers as
    {name: widest reading over the sample, None on an empty sample}; the
    control's is None without one."""
    prog: dict = {k: [] for k in NUMBERS}
    con: dict = {k: [] for k in NUMBERS}
    n = 0
    for prompt, served, lps in pairs:
        lg = _positions(ref, prompt, served)
        n += len(served)
        for k, v in _read(lg, served, lps).items():
            prog[k].append(v)
        if ctrl is not None:
            cl = _positions(ctrl, prompt, served)
            pick = cl.argmax(-1)
            for k, v in _read(lg, pick, _logprob(cl, pick)).items():
                con[k].append(v)

    def widest(d):
        return {k: float(np.concatenate(v).max()) if n else None
                for k, v in d.items()}
    return widest(prog), widest(con) if ctrl is not None else None, n


def checks(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for each number compared."""
    return {k: {"value": numbers[k], "limit": float(limits[k])}
            for k in NUMBERS}


def passes(checked: dict) -> bool:
    """Every number there and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checked.values())
