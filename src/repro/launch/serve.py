"""Batched serving driver: prefill + decode with a request queue.

Two loops share one jitted serve step:

* **fixed batch** (default): prefill all requests at once, decode in
  lockstep — the classic throughput script.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
          --requests 8 --prompt-len 64 --gen 32

* **continuous batching** (``--arrival``): a pool of ``--slots`` decode
  slots; queued prompts are admitted into freed slots *mid-decode*
  (batch-1 prefill inserted into the slot's cache rows), each slot
  tracking its own position / remaining budget / EOS.  One jitted serve
  step runs over the whole slot batch with a vector of per-slot
  positions.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
          --requests 8 --slots 4 --arrival-every 3 --arrival

The serving window rounds up to the kernel block so decode attention
stays on the Pallas fast path, and both loops pass the bucketed
live-window bound (``w_live``) so a mostly-empty ring buffer is cropped
before the kernel — each bucket (powers of two from 2×block) compiles
once.  Row independence of the decode path makes the two loops emit
identical tokens per request for dense/vlm (pinned in
tests/test_serve.py); moe's capacity router couples rows in a batch
(group capacity depends on how many tokens share the group), so its
``--check-parity`` is not bit-exact.
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.kernels.ops import DEFAULT_BLOCK
from repro.models.zoo import get_model
from repro.utils import spans
from repro.utils.compile_cache import use_compile_cache

# families with a dense-style {"k","v"} ring-buffer cache (leading
# layer axis, batch axis 1) — the ones the slot loop can admit into
SLOT_FAMILIES = ("dense", "vlm", "moe")


def round_window(n: int, mult: int = DEFAULT_BLOCK) -> int:
    """Smallest multiple of ``mult`` ≥ n (the kernel-eligible window)."""
    return max(mult, -(-int(n) // mult) * mult)


def live_bucket(n_live: int, window: int) -> int:
    """Power-of-two bucket (floor 2×block) covering ``n_live`` slots.

    The decode fast path crops the cache read to this bound
    (``layers.decode_attention`` ``w_live``); bucketing bounds
    recompiles to log2(window/2·block) + 1 serve-step variants.
    """
    b = 2 * DEFAULT_BLOCK
    while b < n_live:
        b *= 2
    return min(b, window)


def pad_kv_to_window(cache, window: int, axis: int = 2):
    """Zero-pad the ring-buffer K/V leaves of a prefill cache to the
    serving window.

    Only ``"k"``/``"v"`` leaves pad (encdec's precomputed cross
    ``"xk"``/``"xv"`` and SSM states keep their shapes); nested dicts
    (hybrid's ``{"mamba": …, "attn": …}``) recurse.  Padded slots are
    invalid under the position-derived mask until decode writes them.
    """
    out = {}
    for name, leaf in cache.items():
        if isinstance(leaf, dict):
            out[name] = pad_kv_to_window(leaf, window, axis)
        elif name in ("k", "v") and leaf.shape[axis] < window:
            widths = [(0, 0)] * leaf.ndim
            widths[axis] = (0, window - leaf.shape[axis])
            out[name] = jnp.pad(leaf, widths)
        else:
            out[name] = leaf
    return out


def _prefill_batch(cfg, prompts, gen: int):
    """(batch dict, pos0, window) for one prefill of ``prompts``."""
    B, P = prompts.shape
    if cfg.family == "encdec":
        Pe = min(P, cfg.encdec.dec_seq - gen)
        batch = {"audio_embeds": jnp.zeros((B, cfg.encdec.enc_seq,
                                            cfg.d_model), cfg.cdtype),
                 "tokens": prompts[:, :Pe]}
        pos0 = Pe
    else:
        batch = {"tokens": prompts}
        pos0 = P
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (B, cfg.vlm.n_patches, cfg.vlm.d_vision), cfg.cdtype)
            pos0 = P + cfg.vlm.n_patches
    if cfg.family in ("ssm", "hybrid"):
        window = max(pos0 + gen, 2 * cfg.ssm.d_conv if cfg.ssm else 0)
    else:
        window = round_window(pos0 + gen)
    return batch, pos0, window


def run_fixed(cfg, model, params, prompts, gen: int):
    """Lockstep fixed-batch serving.  Returns (tokens (B, gen), stats)."""
    B = prompts.shape[0]
    batch, pos0, window = _prefill_batch(cfg, prompts, gen)
    ring = cfg.family not in ("ssm", "hybrid")

    t0 = time.time()
    logits, cache = jax.jit(model.prefill)(params, batch)
    if ring:
        cache = pad_kv_to_window(cache, window)
    jax.block_until_ready(cache)
    t_prefill = time.time() - t0

    # the cache is donated, as run_arrival donates it: both loops run
    # one compiled step, which writes the cache in place
    serve_step = jax.jit(model.make_serve_step(),
                         static_argnames=("w_live",),
                         donate_argnames=("cache",))
    token = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [token]
    t0 = time.time()
    for t in range(gen - 1):
        pos = pos0 + t
        wl = live_bucket(pos + 1, window) if ring else None
        # slot families take per-row positions, as run_arrival passes
        # them, so both loops run one compiled step: on a TPU a scalar-
        # position cache write rounds differently from a per-row one
        position = (jnp.full((B,), pos, jnp.int32)
                    if cfg.family in SLOT_FAMILIES else jnp.int32(pos))
        token, cache = serve_step(params, cache, token, position,
                                  w_live=wl)
        out_tokens.append(token)
    jax.block_until_ready(token)
    t_decode = time.time() - t0
    stats = {"t_prefill": t_prefill, "t_decode": t_decode,
             "tok_s": B * (gen - 1) / max(t_decode, 1e-9),
             "window": window}
    return jnp.concatenate(out_tokens, axis=1), stats


def slot_fns(model):
    """The slot loop's jitted functions: ``prefill1(params, batch)``,
    ``insert(big, small, slot)`` and ``serve_step(params, cache, token,
    position, w_live)``.  ``insert`` and ``serve_step`` donate the
    cache, so each writes it in place.  Each body counts
    ``serve.trace`` when JAX traces it."""
    step_fn = model.make_serve_step()

    # the bodies run while JAX traces them, so the counter counts traces
    @jax.jit
    def prefill1(params, batch):
        spans.count("serve.trace", fn="prefill1")
        return model.prefill(params, batch)

    @partial(jax.jit, static_argnames=("w_live",), donate_argnames=("cache",))
    def serve_step(params, cache, token, position, w_live):
        spans.count("serve.trace", fn="serve_step", w_live=w_live)
        return step_fn(params, cache, token, position, w_live=w_live)

    @partial(jax.jit, donate_argnames=("big",))
    def insert(big, small, slot):
        spans.count("serve.trace", fn="insert")
        return jax.tree_util.tree_map(
            lambda b, s: jax.lax.dynamic_update_slice_in_dim(
                b, s.astype(b.dtype), slot, axis=1), big, small)

    return prefill1, insert, serve_step


def run_arrival(cfg, model, params, prompts, gen: int, slots: int,
                arrival_every: int = 1, eos_id: int | None = None):
    """Continuous batching: admit queued prompts into freed slots
    mid-decode.

    Request r arrives at decode step ``r * arrival_every``; a free slot
    prefills it (batch-1, compiled once) and its K/V rows are inserted
    into the slot batch's cache.  Every decode step runs ONE jitted
    serve step over all ``slots`` rows with per-slot positions; slots
    whose request finished (budget spent or EOS) idle harmlessly until
    re-admission overwrites their rows.  Returns
    ``(outputs: list[list[int]] per request, stats)``.

    The call, each admission and each decode step are program spans
    (``serve.run_arrival``, ``serve.admit``, ``serve.step`` with its
    ``serve.sync`` readback), and ``stats`` carries their times on
    ``time.perf_counter_ns``: per request ``arrive_ns`` (the start of
    the loop pass it arrived in), ``admit_ns`` and ``first_token_ns``
    (its admission's start and end: the first token is then on the
    host), ``first_step`` and ``last_step`` (the decode steps that gave
    its second and last token, -1 where none did), and ``step_end_ns``
    per decode step.  :func:`latencies` reads them.
    """
    if cfg.family not in SLOT_FAMILIES:
        raise ValueError(
            f"continuous batching needs a dense-style KV cache; "
            f"family {cfg.family!r} is not in {SLOT_FAMILIES}")
    R, P = prompts.shape
    _, pos0_req, window = _prefill_batch(cfg, prompts[:1], gen)
    prefill1, insert, serve_step = slot_fns(model)

    cache = model.init_cache(slots, window)
    token = jnp.zeros((slots, 1), jnp.int32)
    positions = np.zeros(slots, np.int64)
    rid_of = [-1] * slots
    remaining = [0] * slots
    outputs: list[list[int]] = [[] for _ in range(R)]
    arrive_ns, admit_ns, first_token_ns = [0] * R, [0] * R, [0] * R
    first_step, last_step = [-1] * R, [-1] * R
    step_end_ns: list[int] = []
    next_req, arrived, step = 0, 0, 0

    with spans.span("serve.run_arrival", requests=R, slots=slots) as call:
        last_end = call.start_ns
        while next_req < R or any(remaining):
            while arrived < R and arrived * arrival_every <= step:
                arrive_ns[arrived] = last_end
                arrived += 1
            for s in range(slots):
                if (remaining[s] == 0 and next_req < R
                        and next_req * arrival_every <= step):
                    r, next_req = next_req, next_req + 1
                    with spans.span("serve.admit", rid=r, slot=s) as adm:
                        batch, _, _ = _prefill_batch(cfg, prompts[r:r + 1],
                                                     gen)
                        logits, pc = prefill1(params, batch)
                        cache = insert(cache, pad_kv_to_window(pc, window),
                                       jnp.int32(s))
                        first = int(jnp.argmax(logits[0, -1]))
                        outputs[r].append(first)
                        token = token.at[s, 0].set(first)
                        positions[s] = pos0_req
                        rid_of[s], remaining[s] = r, gen - 1
                        if eos_id is not None and first == eos_id:
                            remaining[s] = 0
                    admit_ns[r], first_token_ns[r] = adm.start_ns, adm.end_ns
                    last_end = adm.end_ns
            if not any(remaining):
                step += 1
                continue
            i = len(step_end_ns)
            with spans.span("serve.step", step=i,
                            live=sum(n > 0 for n in remaining)) as sp:
                wl = live_bucket(int(positions.max()) + 1, window)
                token, cache = serve_step(
                    params, cache, token,
                    jnp.asarray(positions, jnp.int32), w_live=wl)
                with spans.span("serve.sync"):
                    tok_host = np.asarray(token[:, 0])
                for s in range(slots):
                    if remaining[s] > 0:
                        r = rid_of[s]
                        outputs[r].append(int(tok_host[s]))
                        if first_step[r] < 0:
                            first_step[r] = i
                        last_step[r] = i
                        positions[s] += 1
                        remaining[s] -= 1
                        if eos_id is not None and tok_host[s] == eos_id:
                            remaining[s] = 0
            step_end_ns.append(sp.end_ns)
            last_end = sp.end_ns
            step += 1
    t_total = (call.end_ns - call.start_ns) / 1e9
    n_tok = sum(len(o) for o in outputs)
    stats = {"t_total": t_total, "decode_steps": len(step_end_ns),
             "tok_s": n_tok / max(t_total, 1e-9), "window": window,
             "arrive_ns": arrive_ns, "admit_ns": admit_ns,
             "first_token_ns": first_token_ns, "first_step": first_step,
             "last_step": last_step, "step_end_ns": step_end_ns}
    return outputs, stats


def latencies(stats) -> dict:
    """Time to first token (from arrival) and the gaps between a
    request's successive tokens, in ms, from :func:`run_arrival`'s
    stats: ``{"ttft_ms": [...], "itl_ms": [...]}``."""
    ends = np.asarray(stats["step_end_ns"], np.int64)
    ttft, itl = [], []
    for a, f, i, j in zip(stats["arrive_ns"], stats["first_token_ns"],
                          stats["first_step"], stats["last_step"]):
        ttft.append((f - a) / 1e6)
        if i >= 0:
            itl.extend(np.diff(np.concatenate([[f], ends[i:j + 1]])) / 1e6)
    return {"ttft_ms": ttft, "itl_ms": itl}


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-backend", default=None,
                    choices=("auto", "kernel", "oracle"),
                    help="override ModelConfig.attn_backend")
    ap.add_argument("--arrival", action="store_true",
                    help="continuous batching: admit requests mid-decode")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots for --arrival")
    ap.add_argument("--arrival-every", type=int, default=1,
                    help="request r arrives at decode step r*this")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--check-parity", action="store_true",
                    help="with --arrival: assert per-request tokens "
                         "match the fixed-batch run (exact for "
                         "dense/vlm; moe routing is batch-coupled)")
    args = ap.parse_args()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.attn_backend is not None:
        cfg = cfg.replace(attn_backend=args.attn_backend)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    R, P = args.requests, args.prompt_len
    rng = np.random.RandomState(args.seed)
    prompts = jnp.asarray(rng.randint(0, cfg.vocab, size=(R, P)),
                          jnp.int32)

    if args.arrival:
        outs, stats = run_arrival(cfg, model, params, prompts, args.gen,
                                  slots=min(args.slots, R),
                                  arrival_every=args.arrival_every,
                                  eos_id=args.eos_id)
        print(f"arch={cfg.name} requests={R} prompt={P} gen={args.gen} "
              f"slots={min(args.slots, R)} window={stats['window']} "
              f"arrival_every={args.arrival_every}")
        print(f"continuous batching: {stats['decode_steps']} decode "
              f"steps, {stats['t_total']:.2f}s "
              f"({stats['tok_s']:.1f} tok/s aggregate)")
        lat = latencies(stats)
        for name, xs in (("TTFT", lat["ttft_ms"]),
                         ("inter-token", lat["itl_ms"])):
            if xs:
                p50, p95 = np.percentile(xs, [50, 95])
                print(f"{name}: p50 {p50:.2f} ms, p95 {p95:.2f} ms "
                      f"over {len(xs)}")
        print("sample:", outs[0][:16])
        if args.check_parity:
            fixed, _ = run_fixed(cfg, model, params, prompts, args.gen)
            ok = all(np.array_equal(np.asarray(fixed[r]),
                                    np.asarray(outs[r], np.int32))
                     for r in range(R))
            print(f"parity vs fixed batch: {'OK' if ok else 'MISMATCH'}")
            if not ok:
                raise SystemExit(1)
    else:
        gen, stats = run_fixed(cfg, model, params, prompts, args.gen)
        print(f"arch={cfg.name} requests={R} prompt={P} gen={args.gen} "
              f"window={stats['window']}")
        print(f"prefill {stats['t_prefill']:.2f}s; decode "
              f"{stats['t_decode']:.2f}s "
              f"({stats['tok_s']:.1f} tok/s aggregate)")
        print("sample:", np.asarray(gen[0])[:16].tolist())


if __name__ == "__main__":
    main()
