"""The one general traffic generator. A traffic mix is a data file of
parameters (benchmark/traffic/<name>.json); everything drawn here comes
from `--seed`, so the same seed and file give the same inputs.

Serve mixes -> `serve_schedule`: due times, prompts, output lengths and
adapter routing of an open-loop (or backlog-fed) request stream.
Train mixes -> `write_corpus`: the WikiText-shaped text the training CLI
tokenizes (copied from tools/e2e_ppl_pipeline.write_synthetic_corpus,
listed in PERF.md's Open questions for deletion there).
"""
from __future__ import annotations

import hashlib
import os

import numpy as np


# --------------------------- distributions ----------------------------------

def draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    """n integer lengths from {"dist": "lognormal", "median", "sigma",
    "min", "max"}, clipped to [min, max] (sigma 0 is a fixed length)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def draw_arrivals(rng, spec: dict, n: int) -> np.ndarray:
    """n due times in seconds from the start of the stream.

    backlog : all due at t = 0 (an offline batch: the queue never empties)
    poisson : exponential gaps at `rate_per_s`
    bursty  : Poisson whose rate alternates between `rate_per_s * high`
              for `on_s` seconds and `rate_per_s * low` for `off_s`
              (mean rate stated by the file, not searched for)
    """
    proc = spec["process"]
    if proc == "backlog":
        return np.zeros(n)
    if proc == "poisson":
        return np.cumsum(rng.exponential(1.0 / spec["rate_per_s"], n))
    if proc == "bursty":
        t, out = 0.0, []
        period = spec["on_s"] + spec["off_s"]
        while len(out) < n:
            on = (t % period) < spec["on_s"]
            rate = spec["rate_per_s"] * (spec["high"] if on else spec["low"])
            t += rng.exponential(1.0 / max(rate, 1e-9))
            out.append(t)
        return np.asarray(out)
    raise ValueError(f"unknown arrival process {proc!r}")


def draw_adapters(rng, spec: dict, n: int) -> list:
    """Per request: an adapter index in 0..resident-1, or -1 for the
    base model. mix zipf: P(rank k) ~ 1/k^s over base-first ranks."""
    k = spec["resident"]
    ids = ([-1] if spec.get("include_base", True) else []) \
        + list(range(k))
    if spec.get("mix", "uniform") == "zipf":
        p = 1.0 / np.arange(1, len(ids) + 1) ** spec.get("zipf_s", 1.0)
    else:
        p = np.ones(len(ids))
    return [ids[int(i)] for i in rng.choice(len(ids), n, p=p / p.sum())]


# --------------------------- serve schedules --------------------------------

def serve_schedule(traffic: dict, vocab: int, seed: int, n: int) -> dict:
    """n requests of the mix. Prompts share a prefix where the file says
    so (`prefix_sharing`: a seeded pool of `pool` prefixes of
    `prefix_len` tokens, taken by a `share` of the requests)."""
    rng = np.random.default_rng([int(seed), 0x5e21e])
    due = draw_arrivals(rng, traffic["arrivals"], n)
    p_len = draw_lengths(rng, traffic["prompt_len"], n)
    o_len = draw_lengths(rng, traffic["output_len"], n)
    adapters = draw_adapters(rng, traffic["adapters"], n)
    ps = traffic.get("prefix_sharing") or {}
    pool = [rng.integers(1, vocab - 1, ps["prefix_len"]).tolist()
            for _ in range(ps.get("pool", 0))]
    prompts = []
    for i in range(n):
        body = rng.integers(1, vocab - 1, int(p_len[i])).tolist()
        if pool and rng.random() < ps["share"]:
            pre = pool[int(rng.integers(0, len(pool)))]
            body = (pre + body)[:max(int(p_len[i]), len(pre) + 1)]
            body = body[:traffic["prompt_len"]["max"]]
        prompts.append(body)
    return {"due_s": due, "prompts": prompts,
            "max_new": [int(x) for x in o_len], "adapter": adapters}


def schedule_digest(sched: dict) -> str:
    h = hashlib.sha256()
    h.update(np.asarray(sched["due_s"], np.float64).tobytes())
    for p, m, a in zip(sched["prompts"], sched["max_new"],
                       sched["adapter"]):
        h.update(np.asarray(p + [m, a], np.int64).tobytes())
    return h.hexdigest()


# --------------------------- train corpus -----------------------------------

def corpus_text(spec: dict, seed: int) -> dict:
    """WikiText-shaped splits: Zipfian unigrams over `vocab_words` words
    with a deterministic bigram continuation taken with probability
    `p_follow` — learnable, so the loss falls within a few steps."""
    nv = spec.get("vocab_words", 400)
    vocab = [f"w{i:03d}" for i in range(nv)]
    p = 1.0 / np.arange(1, nv + 1)
    p /= p.sum()
    follow = np.random.default_rng([int(seed), 0]).integers(0, nv, nv)
    p_follow = spec.get("p_follow", 0.55)

    def gen(n_words, rng):
        # vectorised: draw every decision first, then walk the chain
        take = rng.random(n_words) < p_follow
        fresh = rng.choice(nv, n_words, p=p)
        ids = np.empty(n_words, np.int64)
        w = int(rng.integers(nv))
        for i in range(n_words):
            w = int(follow[w]) if take[i] else int(fresh[i])
            ids[i] = w
        lines, i = [], 0
        lens = rng.integers(8, 24, n_words // 8 + 1)
        for ln in lens:
            if i >= n_words:
                break
            lines.append(" " + " ".join(vocab[j] for j in ids[i:i + ln])
                         + " ")
            i += int(ln)
        return "\n".join(lines) + "\n"

    n = spec["train_words"]
    return {split: gen(m, np.random.default_rng([int(seed), k + 1]))
            for k, (split, m) in enumerate(
                (("train", n), ("valid", max(n // 20, 400)),
                 ("test", max(n // 20, 400))))}


def write_corpus(d: str, spec: dict, seed: int) -> str:
    """Write (or re-check) the corpus of (spec, seed) under d; returns
    the sha256 of its bytes. An existing directory is kept only if its
    recorded hash matches the text the seed gives now."""
    texts = corpus_text(spec, seed)
    h = hashlib.sha256()
    for split in sorted(texts):
        h.update(texts[split].encode())
    digest = h.hexdigest()
    mark = os.path.join(d, "corpus.sha256")
    if os.path.exists(mark) and open(mark).read().strip() == digest \
            and all(os.path.exists(os.path.join(d, f"wiki.{s}.tokens"))
                    for s in texts):
        return digest
    os.makedirs(d, exist_ok=True)
    for split, text in texts.items():
        with open(os.path.join(d, f"wiki.{split}.tokens"), "w") as f:
            f.write(text)
    with open(mark, "w") as f:
        f.write(digest + "\n")
    return digest


def argv_value(argv, flag: str, default=None):
    """The value after `flag` in a CLI argv list (traffic files of train
    mixes carry the CLI's own arguments; sizes are read from them)."""
    return argv[argv.index(flag) + 1] if flag in argv else default
