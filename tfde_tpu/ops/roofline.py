"""Per-op roofline accounting for the attention hot path.

Two halves, one source of truth:

- **Analytic flop model** — `mean_attended_keys` credits causal and
  sliding-window attention with the flops the kernels actually have to do
  (exactly (S+1)/2 in-band keys per query for plain causal; the
  triangle-plus-band mean for windowed), so a windowed model's MFU is
  read against its true useful work instead of the full-causal figure;
  `gpt_train_flops_per_token` is the whole step's count built on it.

- **Tile-visit counter** — the flash kernels decide which (Q-tile, K-tile)
  pairs to execute from `flash_attention._tile_in_band`; the counter
  replays the same predicate statically (`tile_visits`) and records the
  schedule the kernels trace (`measured_tile_visits`, via
  `flash_attention.record_tile_visits` in interpret mode — the causal
  backward additionally bumps a runtime counter from inside its scan
  body). `check_tile_visits` pins the two against the analytic band bound,
  so an attention tile-count regression (e.g. a backward that quietly goes
  back to scanning all tiles) fails tier-1 the same way a collective
  count does (tests/test_roofline.py runs it).

The flop model is plain arithmetic on Python ints — importable with no
device, nothing traced.
"""

from __future__ import annotations

from typing import Optional


def mean_attended_keys(seq: int, causal: bool = True,
                       window: Optional[int] = None) -> float:
    """Mean number of attended (in-band) keys per query position.

    - bidirectional: every query sees all S keys.
    - causal: query i sees i+1 keys -> mean (S+1)/2, the EXACT triangle
      count (not the S/2 approximation).
    - causal + window w: the first w queries are still filling the band
      (i+1 keys), the rest see exactly w -> (w(w+1)/2 + (S-w)w) / S.
    """
    if not causal:
        return float(seq)
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    if window < 1:
        raise ValueError(f"window={window} must be >= 1")
    w = window
    return (w * (w + 1) / 2.0 + (seq - w) * w) / seq


def attention_flops_per_token(attn_width: int, seq: int,
                              causal: bool = True,
                              window: Optional[int] = None) -> float:
    """FORWARD attention-matmul flops per token for one layer.

    Per (query, in-band key) pair each head does 2*head_dim flops in the
    score matmul and 2*head_dim in the value matmul -> 4 * heads *
    head_dim * mean_keys = 4 * attn_width * mean_keys per token
    (attn_width = heads * head_dim, == hidden for every model here).
    Training credit is conventionally 3x this (backward ~2x forward);
    callers apply their own multiplier so a forward-only count can use it too.
    """
    return 4.0 * attn_width * mean_attended_keys(seq, causal, window)


def stacked_attention_flops_per_token(
    attn_width: int, seq: int, depth: int, causal: bool = True,
    window: Optional[int] = None, window_pattern: str = "all",
) -> float:
    """Forward attention-matmul flops per token summed over `depth` layers.

    window_pattern follows models/transformer.Encoder: 'all' gives every
    layer the band; 'alternate' (Gemma-2) windows the EVEN layers and
    leaves the odd layers full causal."""
    if window_pattern not in ("all", "alternate"):
        raise ValueError(f"unknown window_pattern {window_pattern!r}")
    full = attention_flops_per_token(attn_width, seq, causal, None)
    if window is None:
        return depth * full
    banded = attention_flops_per_token(attn_width, seq, causal, window)
    if window_pattern == "alternate":
        n_banded = (depth + 1) // 2  # even layer indices: 0, 2, ...
        return n_banded * banded + (depth - n_banded) * full
    return depth * banded


def gpt_train_flops_per_token(hidden: int, mlp: int, depth: int,
                              seq: int, vocab: int, window=None,
                              window_pattern: str = "all") -> float:
    """Analytic matmul FLOPs per token for one causal-LM fwd+bwd step: qkvo
    + mlp per-layer terms as in BERT; attention matmuls credited by the
    EXACT in-band count from ops/roofline.py — (S+1)/2 mean attended keys
    for plain causal (the flash kernels skip future tiles in forward AND
    backward, so counting full bidirectional attention would inflate MFU
    by ~20% at S=4096; the old half-count 2*S*H was ~1/(2n) conservative
    on the diagonal, now exact), the triangle-plus-band mean for a
    sliding `window`, and the per-layer average when `window_pattern=
    'alternate'` windows only even layers (Gemma-2). Plus
    the tied LM head 2HV; training = 3x forward."""
    per_layer = 8 * hidden * hidden + 4 * hidden * mlp
    attn = stacked_attention_flops_per_token(
        hidden, seq, depth, causal=True, window=window,
        window_pattern=window_pattern,
    )
    return 3.0 * (depth * per_layer + attn + 2 * hidden * vocab)


def tile_visits(seq: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None, causal: bool = True,
                window: Optional[int] = None) -> dict:
    """Static tile-visit counts for one head-slice of flash attention.

    Derived from the SAME `_tile_in_band` predicate the kernels branch on
    (via `flash_attention.bwd_tile_plan`), so these are the tiles the
    compiled forward executes (the lane kernel's loop, the grid kernel's
    `pl.when`) and the causal backward scans
    (the in-band pair list IS its scan schedule). The forward, dq and
    dk/dv passes share one band, hence one count."""
    from tfde_tpu.ops import flash_attention as fa

    plan = fa.bwd_tile_plan(seq, block_q, block_k, causal, window)
    return {
        "block_q": plan["block_q"],
        "block_k": plan["block_k"],
        "grid": plan["grid"],
        "fwd": plan["visits"],
        "bwd_dq": plan["visits"],
        "bwd_dkv": plan["visits"],
        "max_visits_per_q_tile": plan["max_visits_per_q_tile"],
        "max_visits_per_k_tile": plan["max_visits_per_k_tile"],
    }


def max_band_tiles_per_q_tile(block_q: int, block_k: int,
                              window: Optional[int]) -> int:
    """Analytic ceiling on in-band K tiles per Q tile for a windowed band:
    the band behind a Q tile spans block_q + window - 1 rows' worth of
    columns, which straddles at most that many K tiles plus one partial —
    the O(S * window / block^2) bound of the acceptance criterion, per
    Q tile. Full causal has no such cap (the diagonal grows with qi)."""
    if window is None:
        raise ValueError("the per-Q-tile band bound needs a window")
    return (block_q + window - 2) // block_k + 2


def measured_tile_visits(
    seq: int = 512, block_q: int = 64, block_k: int = 64,
    causal: bool = True, window: Optional[int] = None,
    logit_cap: Optional[float] = None, batch: int = 1, heads: int = 2,
    head_dim: int = 8, kv_heads: Optional[int] = None,
) -> dict:
    """Run flash fwd+bwd in interpret mode under the kernel tile-visit
    recorder and return what the kernels actually scheduled: the traced
    forward/backward visit counts plus `bwd_steps_executed` — a runtime
    counter bumped from inside the causal backward's scan body, i.e. the
    number of tile computations that genuinely ran — and, where the lane
    forward ran, `fwd_steps_executed` from inside its K-tile loop."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from tfde_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(0)
    kv = kv_heads or heads
    q = jnp.asarray(rng.standard_normal((batch, seq, heads, head_dim)),
                    jnp.float32)
    k = jnp.asarray(rng.standard_normal((batch, seq, kv, head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((batch, seq, kv, head_dim)),
                    jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal, block_q, block_k, True, window, None, logit_cap
        ).astype(jnp.float32).sum()

    with fa.record_tile_visits() as counts:
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        jax.block_until_ready(grads)
        try:
            jax.effects_barrier()  # flush the debug-callback counter
        except Exception:
            pass
        return dict(counts)


def check_tile_visits(verbose: bool = False) -> list:
    """Pin the flash tile schedule against the analytic band. Returns a
    list of failure strings (empty = pass); tests/test_roofline.py asserts
    it is empty.

    What must hold, per case:
    - the traced forward/backward visit counts equal the static plan
      (same predicate, so a mismatch means the kernels' schedule drifted);
    - the causal backward's runtime-executed scan steps equal the plan
      (the backward provably does NOT visit out-of-band tiles), and so do
      the K steps the lane forward's loop ran;
    - causal visits are the exact triangle count (~half the grid);
    - windowed visits respect the O(S * window / block^2) ceiling per
      Q tile.
    """
    failures = []
    cases = [
        # (seq, block, window, kv_heads) — multi-tile, MHA and GQA
        (512, 64, None, None),
        (512, 64, 128, None),
        (768, 128, 256, 1),
    ]
    for seq, block, window, kv_heads in cases:
        name = f"s{seq}b{block}w{window}kv{kv_heads}"
        static = tile_visits(seq, block, block, True, window)
        measured = measured_tile_visits(
            seq=seq, block_q=block, block_k=block, window=window,
            kv_heads=kv_heads,
        )
        n = seq // block
        if window is None:
            expect = n * (n + 1) // 2  # exact causal triangle
            if static["fwd"] != expect:
                failures.append(
                    f"{name}: causal band is {static['fwd']} tiles, "
                    f"expected the exact triangle {expect}"
                )
        else:
            ceiling = max_band_tiles_per_q_tile(block, block, window)
            if static["max_visits_per_q_tile"] > ceiling:
                failures.append(
                    f"{name}: {static['max_visits_per_q_tile']} K tiles "
                    f"per Q tile exceeds the band ceiling {ceiling}"
                )
            if static["fwd"] > n * ceiling:
                failures.append(
                    f"{name}: total visits {static['fwd']} exceed "
                    f"n_q * ceiling = {n * ceiling}"
                )
        for key in ("fwd", "bwd_dq", "bwd_dkv"):
            got = measured.get(f"{key}_visits")
            if got != static[key]:
                failures.append(
                    f"{name}: traced {key} visits {got} != static plan "
                    f"{static[key]}"
                )
        if measured.get("fwd_path") == "lane" \
                and measured.get("fwd_steps_executed") != static["fwd"]:
            failures.append(
                f"{name}: the lane forward executed "
                f"{measured.get('fwd_steps_executed')} K steps for one "
                f"block of heads, plan says {static['fwd']}"
            )
        executed = measured.get("bwd_steps_executed")
        if executed != static["bwd_dq"]:
            failures.append(
                f"{name}: backward executed {executed} scan steps, "
                f"plan says {static['bwd_dq']} — the backward is visiting "
                f"tiles outside the band (or skipping in-band ones)"
            )
        if verbose:
            print(f"{name}: grid={static['grid']} visits={static['fwd']} "
                  f"executed={executed}")
    return failures
