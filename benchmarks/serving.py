"""Serving under live ingest: sustained top-k QPS + tail latency while
``svd_update`` streams in the background, plus the R7 memory story.

A recommender front end answers request waves against the CURRENT
snapshot while an ingest thread folds fresh batches in and publishes
them between waves (the double-buffered swap — readers never see a torn
state).  This benchmark reports, per universe size:

* sustained QPS and p50/p99 wave latency over ``waves`` request waves
  of ``batch`` queries each, with the ingest thread running;
* ``fused_oracle_match`` — the fused kernel (interpret mode, the actual
  kernel body) against the jnp oracle on a slice of the LIVE factors:
  bit-identical values and indices, the acceptance gate;
* int8 serving vs f32: top-k id overlap and ``rel_err_topk`` of the
  returned scores;
* ``r7_peak_b`` (the plan's closed-form serving peak) next to
  ``r7_expected_b``, the same number hand-computed from primitive
  terms — CI asserts they are equal, the R6/R5d precedent.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import jax

from repro import obs
from repro.core import sparse
from repro.core.api import (ServeTopKConfig, SolveConfig, serve_init,
                            serve_topk, svd_init, svd_update)
from repro.serve import ranker as ranker_mod
from repro.kernels import ref as kref
from repro.kernels import topk_score as tks

RANK = 16
BATCH = 32
K_TOP = 10
BLOCK_N = 512


def _deltas(n, num_batches, rows, density, seed):
    """COO row deltas over an n-column universe (sparse: universes are
    large, interactions are not)."""
    out = []
    for i in range(num_batches):
        coo = sparse.ensure_full_row_rank(
            sparse.random_bipartite(rows, n, density, seed=seed + i,
                                    weighted=True), seed=seed + i)
        out.append(coo)
    return out


def _fused_oracle_match(snapshot, queries_scaled, k_top):
    """Run the REAL kernel body on a slice of the live factors vs the
    oracle — bit-identical or the benchmark fails its gate.  On a TPU the
    kernel is compiled; elsewhere it runs in interpret mode, where the
    slice keeps emulation tractable at any N."""
    n_slice = min(snapshot.v.shape[0], 4 * BLOCK_N)
    v = snapshot.v[:n_slice]
    valid = min(snapshot.n, n_slice)
    qs_pad = np.zeros((8, max(v.shape[1], 128)), np.float32)
    qs_pad[:queries_scaled.shape[0], :v.shape[1]] = queries_scaled
    v_pad = np.zeros((n_slice, max(v.shape[1], 128)), np.float32)
    v_pad[:, :v.shape[1]] = np.asarray(v)
    got = tks.topk_score(
        jax.numpy.asarray(qs_pad), jax.numpy.asarray(v_pad),
        jax.numpy.ones((n_slice, 1), jax.numpy.float32),
        valid, 0, k_top=k_top, block_n=BLOCK_N,
        interpret=jax.default_backend() != "tpu")
    want = kref.topk_score(jax.numpy.asarray(qs_pad),
                           jax.numpy.asarray(v_pad), k_top, valid_n=valid)
    return int(np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
               and np.array_equal(np.asarray(got[1]), np.asarray(want[1])))


def run(universes=(200_000,), rank=RANK, batch=BATCH, k_top=K_TOP,
        waves=60, num_batches=6, ingest_rows=64, blocks=8,
        density=1e-3, seed=7, verbose=True):
    out = []
    for n in universes:
        cfg = SolveConfig(method="none", truncate_rank=rank,
                          num_blocks=blocks, stream_backend="single")
        deltas = _deltas(n, num_batches, ingest_rows,
                         min(density, 2e5 / n), seed)
        state = svd_init(n, cfg)
        state = svd_update(state, deltas[0], cfg).state  # warm compile

        scfg = ServeTopKConfig(batch_size=batch, k_top=k_top,
                               block_n=BLOCK_N)
        handle = serve_init(state, scfg)
        rng = np.random.default_rng(seed)
        qs = [rng.standard_normal((batch, rank), np.float32)
              for _ in range(8)]
        serve_topk(handle, qs[0])  # warm the query path too

        # -- background ingest: fold + publish between request waves --
        stop = threading.Event()
        commits = [0]

        def ingest_loop():
            # streams off the latest ingested STATE (the snapshot only
            # carries what queries need), publishing after every fold
            i = 0
            while not stop.is_set():
                i += 1
                ingest_loop.state = svd_update(
                    ingest_loop.state, deltas[i % num_batches], cfg).state
                handle.commit(ingest_loop.state)
                commits[0] += 1

        ingest_loop.state = state
        t = threading.Thread(target=ingest_loop)
        t.start()

        # -- the measured query loop --
        lat = []
        t_all0 = time.perf_counter()
        for w in range(waves):
            q = qs[w % len(qs)]
            t0 = time.perf_counter()
            res = serve_topk(handle, q)
            jax.block_until_ready(res.scores)
            lat.append(time.perf_counter() - t0)
        t_all = time.perf_counter() - t_all0
        stop.set()
        t.join(timeout=120)

        qps = waves * batch / t_all
        p50 = float(np.percentile(lat, 50) * 1e6)
        p99 = float(np.percentile(lat, 99) * 1e6)
        final_version = handle.version

        # -- acceptance gates --
        snap = handle.read()
        scaled = np.asarray(qs[0][:8]) * np.asarray(snap.s)[None, :]
        match = _fused_oracle_match(snap, scaled.astype(np.float32), k_top)

        # int8 vs f32 on the SAME final state version
        h8 = serve_init(ingest_loop.state, scfg, quantize=True)
        hf = serve_init(ingest_loop.state, scfg)
        full = serve_topk(hf, qs[0])
        q8 = serve_topk(h8, qs[0])
        overlap = float(np.mean([
            len(set(np.asarray(full.indices)[i]) &
                set(np.asarray(q8.indices)[i])) / k_top
            for i in range(batch)]))
        denom = float(np.abs(np.asarray(full.scores)).max())
        rel = float(np.abs(np.asarray(q8.scores)
                           - np.asarray(full.scores)).max() / denom)

        # -- obs disabled-mode overhead: serve_topk (whose only obs
        # cost is one enabled() check) vs the direct scoring path the
        # serving engine shipped with, interleaved A/B on the now-quiet
        # handle.  min-of-rounds p99 keeps the <1% CI gate stable
        # against scheduler jitter.
        assert not obs.enabled(), "obs must stay off for the A/B"
        ab_waves = max(waves, 100)
        base_p99s, off_p99s = [], []
        for _ in range(3):
            base_lat, off_lat = [], []
            for w in range(ab_waves):
                q = qs[w % len(qs)]
                t0 = time.perf_counter()
                r = ranker_mod.score_topk(
                    handle.read(), q, k_top, block_n=BLOCK_N,
                    sharded=handle.plan.backend == "shard_map",
                    use_kernel=handle.config.use_kernel)
                jax.block_until_ready(r.scores)
                base_lat.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                r = serve_topk(handle, q)
                jax.block_until_ready(r.scores)
                off_lat.append(time.perf_counter() - t0)
            base_p99s.append(float(np.percentile(base_lat, 99) * 1e6))
            off_p99s.append(float(np.percentile(off_lat, 99) * 1e6))
        p99_base = min(base_p99s)
        p99_off = min(off_p99s)

        # -- R7: plan peak vs the hand-computed closed form --
        width = -(-n // blocks)
        n_pad = blocks * width
        expected = (4 * n_pad * rank                       # resident v
                    + 4 * batch * (rank                    # folded queries
                                   + BLOCK_N               # one score tile
                                   + 2 * k_top             # running top-k
                                   + 2 * (k_top + BLOCK_N)))  # merge cands
        peak = handle.plan.peak_bytes

        derived = (f"qps={qps:.1f};p50_us={p50:.1f};p99_us={p99:.1f}"
                   f";fused_oracle_match={match}"
                   f";int8_overlap={overlap:.3f};rel_err_topk={rel:.3e}"
                   f";r7_peak_b={peak};r7_expected_b={expected}"
                   f";p99_base_us={p99_base:.1f};p99_off_us={p99_off:.1f}"
                   f";ingest_commits={commits[0]}"
                   f";served_version={final_version}")
        out.append({"name": f"serve_topk_{batch}x{n}",
                    "seconds": float(np.mean(lat)), "derived": derived})
        if verbose:
            print(f"  universe {n:>9,} cols: {qps:8.1f} qps | p50 "
                  f"{p50:8.1f}us p99 {p99:8.1f}us | {commits[0]} ingests "
                  f"published | fused==oracle: {bool(match)} | int8 "
                  f"overlap {overlap:.2f} | R7 {peak:,}B "
                  f"(expected {expected:,}B) | obs-off p99 "
                  f"{p99_off:.0f}us vs base {p99_base:.0f}us", flush=True)
    return out


def main(full: bool = False):
    kw = ({"universes": (200_000, 1_000_000), "waves": 120}
          if full else {})
    return run(**kw)


if __name__ == "__main__":
    import sys
    main(full="--full" in sys.argv)
