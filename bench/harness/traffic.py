"""The benchmark's own copy of the serve loop's request generator.

``launch/serve._make_requests`` turns the serve flags and ``--seed`` into
the request list the window serves.  This copy regenerates the same list
from the same flags, so the harness can (a) check the program's counters
against it (requests, prompt tokens, decode tokens) and (b) hand the plain
reference the prompts without taking anything the program made.  If the
program's generator changes, the counters disagree with this copy and the
run is marked failed: the yardstick does not move unnoticed.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Req:
    id: int
    prompt: np.ndarray
    gen: int
    priority: int
    arrival: int


def make_requests(flags: dict, n: int, seed: int, vocab: int) -> list:
    """Same draws, in the same order, from the same numpy streams as the
    serve loop: seed + 1 for prefix, lengths and tokens; seed + 2 for
    priority classes; seed + 3 for Poisson arrivals."""
    prompt = int(flags["--prompt"])
    gen = int(flags["--gen"])
    shared_len = int(flags.get("--shared-prefix", 0))
    rng = np.random.default_rng(seed + 1)
    p_buckets = sorted({max(1, prompt // 2), max(1, 3 * prompt // 4), prompt})
    g_buckets = sorted({max(1, gen // 2), gen})
    shared = None
    if shared_len:
        if shared_len >= prompt:
            raise ValueError("--shared-prefix must leave room for a tail")
        shared = rng.integers(0, vocab, size=(shared_len,))
    prios = [0] * n
    classes = int(flags.get("--priority-classes", 1))
    if classes > 1:
        prios = np.random.default_rng(seed + 2).integers(
            0, classes, size=n).tolist()
    arrivals = [0] * n
    rate = float(flags.get("--arrival-rate", 0.0))
    if rate > 0:
        if flags.get("--trace", "uniform") == "burst":
            bsz = max(1, int(flags.get("--burst-size", 4)))
            gap = max(1, round(bsz / rate))
            arrivals = [(i // bsz) * gap for i in range(n)]
        else:
            gaps = np.random.default_rng(seed + 3).exponential(1.0 / rate,
                                                               size=n)
            arrivals = np.floor(np.cumsum(gaps)).astype(int).tolist()
    out = []
    for i in range(n):
        plen = int(rng.choice(p_buckets))
        glen = int(rng.choice(g_buckets))
        if shared is None:
            toks = rng.integers(0, vocab, size=(plen,))
        else:
            plen = max(plen, shared_len + 1)
            tail = rng.integers(0, vocab, size=(plen - shared_len,))
            toks = np.concatenate([shared, tail])
        out.append(Req(i, np.asarray(toks, np.int32), glen, int(prios[i]),
                       int(arrivals[i])))
    return out


def argv(flags: dict) -> list:
    """Serve-flag dict -> argv list (``True`` is a bare switch)."""
    out = []
    for k, v in flags.items():
        if v is True:
            out.append(k)
        elif v is not False and v is not None:
            out += [k, str(v)]
    return out


def chunk_shapes(flags: dict, reqs: list, with_hits: bool) -> set:
    """Prefill chunk lengths the serve loop can run for ``reqs``: chunks
    of --prefill-chunk from position 0, and, where a shared prefix can be
    found in the prefix cache, from the end of its whole pages, the first
    chunk trimmed onto the chunk grid."""
    chunk = int(flags["--prefill-chunk"])
    page = int(flags["--page-size"])
    shared = int(flags.get("--shared-prefix", 0))
    starts = {0}
    if with_hits and shared and flags.get("--prefix-cache", True):
        starts.add(shared // page * page)
    out = set()
    for r in reqs:
        for pos in starts:
            while pos < len(r.prompt):
                c = min(chunk - pos % chunk, len(r.prompt) - pos)
                out.add(c)
                pos += c
    return out


def warmup_flags(flags: dict, reqs: list, n: int, vocab: int) -> dict:
    """Flags of the set-up pass: the window's flags with ``n`` requests and
    the first seed whose requests hold every prompt length and run every
    prefill chunk length the window can.  The pool's layout and the decode
    batch are the window's, so the decode step is the window's too."""
    need = chunk_shapes(flags, reqs, with_hits=True)
    lens = {len(r.prompt) for r in reqs}
    for s in range(1000):
        wr = make_requests(flags, n, s, vocab)
        if need <= chunk_shapes(flags, wr, False) \
                and lens <= {len(r.prompt) for r in wr}:
            return dict(flags, **{"--seed": s, "--requests": n})
    raise ValueError(f"no warm-up seed below 1000 holds prompt lengths "
                     f"{sorted(lens)} and chunks {sorted(need)}")


def _counts(values: list, n: int) -> dict:
    """Largest-remainder split of n draws over equally likely ``values``
    (a value that appears twice is twice as likely)."""
    share = {}
    for v in values:
        share[v] = share.get(v, 0) + n / len(values)
    out = {v: int(s) for v, s in share.items()}
    left = n - sum(out.values())
    for v in sorted(share, key=lambda v: (out[v] - share[v], v))[:left]:
        out[v] += 1
    return out


def program_seed(flags: dict, n: int, seed: int, vocab: int) -> int:
    """The serve loop's seed for the benchmark's ``seed``: the first seed of
    a fixed pseudo-random sequence drawn from ``seed`` whose requests hold
    exactly the expected number of each prompt length and of each output
    length.  Every seed then serves the same sizes, in another order and
    with other tokens."""
    import hashlib
    prompt, gen = int(flags["--prompt"]), int(flags["--gen"])
    shared = int(flags.get("--shared-prefix", 0))
    p_b = sorted({max(1, prompt // 2), max(1, 3 * prompt // 4), prompt})
    plens = [max(b, shared + 1) if shared else b for b in p_b]
    g_b = sorted({max(1, gen // 2), gen})
    want_p, want_g = _counts(plens, n), _counts(g_b, n)
    want_p = {k: v for k, v in want_p.items() if v}
    want_g = {k: v for k, v in want_g.items() if v}
    for j in range(100000):
        h = hashlib.sha256(f"{int(seed)}:{j}".encode()).digest()
        s = int.from_bytes(h[:4], "little") % (2 ** 31)
        p, g = {}, {}
        for r in make_requests(flags, n, s, vocab):
            p[len(r.prompt)] = p.get(len(r.prompt), 0) + 1
            g[r.gen] = g.get(r.gen, 0) + 1
        if p == want_p and g == want_g:
            return s
    raise ValueError(f"no seed with the expected sizes for seed {seed}")
