"""Seeded request traces for the CPU-sized serving drills and tests.

The toy model (:func:`tiny_config`), the seeded trace with staggered
arrivals (:func:`build_requests`; requests arrive in pairs every
``arrival_every`` engine steps), its per-replica split and merge, and
the nearest-rank percentile the serving reports share (:func:`pctl`).
"""

from __future__ import annotations

from flashmoe_tpu.config import MoEConfig


def tiny_config(*, hidden: int = 64, experts: int = 4, layers: int = 2,
                vocab: int = 256) -> MoEConfig:
    """The CPU-sized serving drill model (dropless — the engine's
    requirement)."""
    import jax.numpy as jnp

    return MoEConfig(
        num_experts=experts, expert_top_k=min(2, experts),
        hidden_size=hidden, intermediate_size=2 * hidden,
        sequence_len=128, num_layers=layers, moe_frequency=2,
        vocab_size=vocab, num_heads=2, drop_tokens=False,
        dtype=jnp.float32, param_dtype=jnp.float32)


def build_requests(n: int, *, vocab: int, prompt_len: int,
                   max_new: int, seed: int, arrival_every: int,
                   temperature: float = 0.0,
                   repetitive: bool = False):
    """The seeded trace: ``n`` requests with deterministic prompts and
    staggered arrivals (one PAIR of arrivals every ``arrival_every``
    engine steps).  ``repetitive`` tiles each prompt from a per-request
    random bigram motif instead of i.i.d. tokens — the speculative
    drills' trace, where the n-gram drafter has suffix matches to
    propose from (an i.i.d. prompt never drafts, which would drive the
    no-op path)."""
    import jax

    from flashmoe_tpu.serving.engine import Request

    if repetitive:
        motif = jax.random.randint(
            jax.random.PRNGKey(seed), (n, 2), 0, vocab)
        reps = -(-prompt_len // 2)
        toks = [([int(t) for t in motif[i]] * reps)[:prompt_len]
                for i in range(n)]
    else:
        toks = jax.random.randint(
            jax.random.PRNGKey(seed), (n, prompt_len), 0, vocab)
    reqs = [Request(rid=i, prompt=tuple(int(t) for t in toks[i]),
                    max_new_tokens=max_new, temperature=temperature,
                    seed=seed + i)
            for i in range(n)]
    arrivals = [(i // 2) * arrival_every for i in range(n)]
    return reqs, arrivals


def pctl(values, q: float):
    """Nearest-rank percentile (None on empty) — THE serving
    percentile: the `observe --serving` report and the quantile
    sketch's exact regime both use this one definition, so no two
    surfaces can disagree about what p99 means."""
    if not values:
        return None
    v = sorted(values)
    return round(v[min(len(v) - 1, int(q * len(v)))], 3)


def split_requests(n: int, *, replicas: int, vocab: int,
                   prompt_len: int, max_new: int, seed: int,
                   arrival_every: int, temperature: float = 0.0):
    """Deterministic per-replica trace split: replica ``r``'s trace is
    seeded with ``fold_in(PRNGKey(seed), r)``, so N independent drill
    processes (one per replica) generate disjoint, reproducible loads
    with no coordination — and their obs artifacts merge cleanly
    (``observe --merge``) because rids are globally unique
    (``rid * replicas + r``).  Returns ``[(requests, arrivals), ...]``,
    one pair per replica; requests total ``n`` (the remainder spreads
    over the lowest replica ids)."""
    import dataclasses

    import jax

    if replicas < 1:
        raise ValueError(f"replicas={replicas} must be >= 1")
    out = []
    for r in range(replicas):
        count = n // replicas + (1 if r < n % replicas else 0)
        sub = int(jax.random.fold_in(
            jax.random.PRNGKey(seed), r)[0]) % (2**31 - 1)
        reqs, arrivals = build_requests(
            count, vocab=vocab, prompt_len=prompt_len, max_new=max_new,
            seed=sub, arrival_every=arrival_every,
            temperature=temperature)
        reqs = [dataclasses.replace(q, rid=q.rid * replicas + r)
                for q in reqs]
        out.append((reqs, arrivals))
    return out


def merge_traces(splits):
    """Merge per-replica traces back into one arrival-ordered stream
    (ties break on rid — deterministic): what a single fabric front
    door submits when the split generated the load."""
    merged = []
    for reqs, arrivals in splits:
        merged.extend(zip(arrivals, reqs))
    merged.sort(key=lambda p: (p[0], p[1].rid))
    return [q for _, q in merged], [a for a, _ in merged]
