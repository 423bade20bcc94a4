"""The one traffic generator: a mix is a data file, this reads it.

A serving mix gives a rate, how prompts share heads and the
distributions of prompt-tail and output lengths.  The multiset of (head,
tail length, output length) is a quantile grid of those distributions,
paired and timed by the mix's own ``schedule_seed``: neither the work
nor when it arrives depends on ``--seed``, which draws the token ids, so
two runs differ in content, never in the amount of work or in how it
bunches.  Another draw of the schedule is another mix file.

A training mix gives the batch geometry and a data process; the batches
of a run are drawn from the seed and the step number alone.
"""

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def seed_words(seed, *stream):
    """SeedSequence entropy for one stream of one run; any whole number
    is taken (the driver's seeds pass 2**31)."""
    return [abs(int(seed)), *stream]


def length_grid(spec, n):
    """n lengths at the mid-quantiles (i + 0.5) / n of ``spec``:
    {"dist": "lognormal", "median", "sigma", "min", "max"}."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(v)) for v in q])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def head_quota(count, zipf_s, n):
    """n head indices with Zipf(``zipf_s``) popularity over ``count``
    heads, by largest remainder so the counts are exact and fixed."""
    w = 1.0 / np.arange(1, count + 1) ** zipf_s
    share = w / w.sum() * n
    base = np.floor(share).astype(int)
    for i in np.argsort(-(share - base), kind="stable")[:n - base.sum()]:
        base[i] += 1
    return np.repeat(np.arange(count), base)


def arrival_times(rng, n, seconds):
    """n due times in [0, seconds), ascending: a Poisson process given
    its count (n + 1 exponential gaps scaled to fill the window are the
    order statistics of n uniform draws)."""
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] / gaps.sum() * seconds


def serve_schedule(mix, vocab, seed, seconds):
    """The requests of one run of a serving mix.

    Which request (head, tail length, output length) is due when is drawn
    with the mix's ``schedule_seed``: near its knee a server's tails are
    a function of the arrival sequence (the same requests in another
    order of 5-second stretches moved ``ttft_p90_ms`` from 690 to 1250
    ms, PERF.md, PR 24), so a schedule redrawn by ``--seed`` measures
    the draw.  ``--seed`` draws every token id (and the weights), which
    changes what is computed and not how long it takes.

    Returns {"due": [n] seconds from the window's start, ascending,
    "prompts": n int32 arrays, "max_new": [n], "head": [n] (-1 where the
    prompt shares nothing), "heads": [count, tokens] the shared heads,
    "spare_first": unused first-token ids for warm-up prompts}.
    """
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    fixed = np.random.default_rng(int(mix["schedule_seed"]))
    tails = length_grid(mix["prompt_tail"], n)[fixed.permutation(n)]
    outs = length_grid(mix["output"], n)[fixed.permutation(n)]
    shared = mix.get("shared_heads") or {"count": 0, "tokens": 0}
    if shared["count"]:
        head = head_quota(shared["count"], shared["zipf_s"],
                          n)[fixed.permutation(n)]
    else:
        head = np.full(n, -1)
    due = arrival_times(fixed, n, seconds)

    rng = np.random.default_rng(seed_words(seed, 1))
    heads = rng.integers(0, vocab, (shared["count"], shared["tokens"]),
                         dtype=np.int32)
    firsts = rng.permutation(vocab).astype(np.int32)
    prompts = []
    for i in range(n):
        tail = rng.integers(0, vocab, tails[i], dtype=np.int32)
        if head[i] < 0:
            tail[0] = firsts[i]  # unique first token: nothing to share
            prompts.append(tail)
        else:
            prompts.append(np.concatenate([heads[head[i]], tail]))
    return {"due": due, "prompts": prompts, "max_new": outs, "head": head,
            "heads": heads, "spare_first": firsts[n:]}


class BigramChain:
    """Token sequences from a seeded first-order chain over all ``vocab``
    ids: each id has ``branching`` successors drawn with Zipf(``zipf_s``)
    popularity over a seeded permutation of the ids, so the unigram
    distribution is skewed (the loss falls within tens of steps) and the
    bigram structure is there to be learned after it."""

    def __init__(self, vocab, seed, branching=4, zipf_s=1.0):
        rng = np.random.default_rng(seed_words(seed, 2))
        p = 1.0 / np.arange(1, vocab + 1) ** zipf_s
        ranked = rng.permutation(vocab)
        self.succ = ranked[rng.choice(vocab, (vocab, branching),
                                      p=p / p.sum())].astype(np.int32)
        self.vocab, self.branching, self.seed = vocab, branching, seed

    def batch(self, step, sequences, seq_len):
        """(tokens, labels), both [sequences, seq_len] int32, labels the
        tokens shifted left by one; a function of (seed, step) alone."""
        rng = np.random.default_rng(seed_words(self.seed, 3, step))
        pick = rng.integers(0, self.branching, (seq_len, sequences))
        seq = np.empty((seq_len + 1, sequences), np.int32)
        seq[0] = rng.integers(0, self.vocab, sequences)
        for t in range(seq_len):
            seq[t + 1] = self.succ[seq[t], pick[t]]
        seq = np.ascontiguousarray(seq.T)
        return seq[:, :-1], seq[:, 1:]
