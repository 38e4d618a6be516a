#!/usr/bin/env python3
"""What it costs a fresh serving prefill to write its K/V into the paged
pools, by hand, on the chip, at the shapes of one attention layer of the three
cells whose prefill is fresh (PERF.md section 6, PR 46):

    python3 kv_write_on_chip.py [--cases swa_full,swa_ring,gdn_full,mla_latent] [--widths 1024,4096,8192,16384] [--shares 1,0.7]
    JAX_PLATFORMS=cpu python3 kv_write_on_chip.py --aot
    JAX_PLATFORMS=cpu python3 kv_write_on_chip.py --tiny

One call of ``S`` positions of which ``L`` are real, one request (a prefill is
serial), the pools DONATED as the engine donates them, at the cell's pool
size. The forms, a line each case, width and length:

* ``row`` — ``ops.attention.paged_update`` / ``latent_update`` told the call is
  NOT fresh: a (block, offset) a position, one row of ``head_dim`` a write
  where the pool is heads first — what every prefill ran before PR 46;
* ``block`` — the same functions told it IS fresh: what the program runs now
  (whole blocks at their table entries, blocks past the length dropped; a
  ring: ``min(S, ring)`` rows gathered in ring order, one slice);
* ``garbage`` — whole blocks, those past the length sent to block 0 (indices
  no longer unique);
* ``loop`` — a ``fori_loop`` of one ``dynamic_update_slice`` a live block;
* ``roll`` (a ring) — the ring as a rotation of the prompt's last ``ring``
  rows: a dynamic slice, a doubled concatenate and a second dynamic slice in
  the gather's place.

A line holds the milliseconds a call and the nanoseconds a position (K and V
both), and every form says once a case whether its COMPILED program holds a
copy of a pool (an instruction that yields a pool-sized array other than the
update itself) and its temporaries. ``--aot`` compiles for a described v5e
and prints that alone; ``--tiny`` holds every form to ``row`` on the rows a
read can reach, on the CPU, and prints no time."""

from __future__ import annotations

import argparse
import json
import math
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp

# KV heads (0: a latent row, no heads), row width, slots, positions a slot,
# ring (0: a full layer's pool), the cell
CASES = {
    "swa_full": (4, 128, 32, 16384, 0, "serve-swa-moe-mixed-sat"),
    "swa_ring": (4, 128, 32, 16384, 4096, "serve-swa-moe-mixed-sat"),
    "gdn_full": (2, 256, 64, 16384, 0, "serve-gdn-moe-sat"),
    "mla_latent": (0, 640, 32, 16384, 0, "serve-mla-moe-longctx-sat"),
}
TINY = {
    "swa_full": (4, 128, 3, 64, 0, "tiny"),
    "swa_ring": (4, 128, 3, 64, 16, "tiny"),
    "gdn_full": (2, 8, 3, 64, 0, "tiny"),
    "mla_latent": (0, 24, 3, 64, 0, "tiny"),
}
BLOCK = 16
SLOT = 1  # the seat the call fills: not the first, so a clamp would show


def _state(case, fresh, table, lengths):
    from accelerate_tpu.ops.attention import PagedKVState, pool_heads_first

    hkv, d, slots, ctx, ring, _ = case
    return PagedKVState(
        block_table=table, cache_len=jnp.zeros_like(lengths), lengths=lengths,
        num_blocks=slots * (ctx // BLOCK) + 1, block_size=BLOCK, fresh=fresh,
        heads_first=bool(hkv) and pool_heads_first(hkv, d), ring=ring,
        num_slots=slots, slot=jnp.full_like(lengths, SLOT))


def _live_blocks(state, n):
    """(pool block of each of the call's n blocks, whether it starts before
    the length) of a one-request call."""
    j = jnp.arange(n, dtype=jnp.int32)
    return state.block_table[0, :n], j * BLOCK < state.lengths[0]


def _garbage(pool, rows, state):
    from accelerate_tpu.ops.attention import _as_blocks, _flat_pool

    blocks = _as_blocks(rows, state)[0]
    flat = _flat_pool(pool, blocks.ndim - 1)
    at, live = _live_blocks(state, blocks.shape[0])
    return flat.at[jnp.where(live, at, 0)].set(blocks).reshape(pool.shape)


def _loop(pool, rows, state):
    from accelerate_tpu.ops.attention import _as_blocks, _flat_pool

    blocks = _as_blocks(rows, state)[0]
    flat = _flat_pool(pool, blocks.ndim - 1)
    at, _ = _live_blocks(state, blocks.shape[0])
    zeros = (0,) * (flat.ndim - 1)

    def body(j, flat):
        return jax.lax.dynamic_update_slice(
            flat, jax.lax.dynamic_slice_in_dim(blocks, j, 1), (at[j], *zeros))

    live = -(-state.lengths[0] // BLOCK)
    return jax.lax.fori_loop(0, live, body, flat).reshape(pool.shape)


def _roll(pool, rows, state):
    from accelerate_tpu.ops.attention import _as_blocks, _flat_pool, _ring_view

    view = _ring_view(state)
    s, ring = rows.shape[1], state.ring
    if s > ring:
        start = jnp.maximum(state.lengths[0] - ring, 0)
        last = jax.lax.dynamic_slice_in_dim(rows[0], start, ring)
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([last, last]), ring - start % ring, ring)[None]
    blocks = _as_blocks(rows, view)[0]
    return jax.lax.dynamic_update_slice(
        _flat_pool(pool, 3), blocks, (view.block_table[0, 0], 0, 0, 0)
    ).reshape(pool.shape)


def forms_of(case):
    """{form: jitted (pools..., rows..., table, lengths) -> pools}, the pools
    donated."""
    from accelerate_tpu.ops.attention import latent_update, paged_update

    hkv, _, _, _, ring, _ = case

    def program(fresh):
        def call(*args):
            *arrays, table, lengths = args
            state = _state(case, fresh, table, lengths)
            if not hkv:
                return (latent_update(arrays[0], arrays[1], state),)
            return paged_update(*arrays, state, ring=bool(ring))
        return call

    def local(put):
        def call(*args):
            *arrays, table, lengths = args
            state = _state(case, True, table, lengths)
            n = len(arrays) // 2
            return tuple(put(pool, rows, state)
                         for pool, rows in zip(arrays[:n], arrays[n:]))
        return call

    forms = {"row": program(False), "block": program(True)}
    if ring:
        forms["roll"] = local(_roll)
    else:
        forms["garbage"] = local(_garbage)
        forms["loop"] = local(_loop)
    pools = 2 if hkv else 1
    return {name: jax.jit(fn, donate_argnums=tuple(range(pools)))
            for name, fn in forms.items()}


def shapes_of(case, width):
    """(pool shape, rows shape, table shape) of a case at a bucket."""
    from accelerate_tpu.ops.attention import pool_heads_first

    hkv, d, slots, ctx, ring, _ = case
    blocks = slots * ((ring or ctx) // BLOCK) + 1
    if not hkv:
        return (blocks, BLOCK, d), (1, width, d), (1, ctx // BLOCK)
    block = (hkv, BLOCK, d) if pool_heads_first(hkv, d) else (BLOCK, hkv, d)
    return (blocks, *block), (1, width, hkv, d), (1, ctx // BLOCK)


def specs_of(case, width, sharding=None):
    """The abstract arguments of a form at a bucket: pools, rows, the table,
    the length (``sharding``: a described chip's, for ``--aot``)."""
    pool, rows, table = shapes_of(case, width)
    n = 2 if case[0] else 1

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return ([spec(pool, jnp.bfloat16)] * n + [spec(rows, jnp.bfloat16)] * n
            + [spec(table, jnp.int32), spec((1,), jnp.int32)])


def pool_copies(text: str, pool_shape) -> list[str]:
    """The instructions of a compiled program that yield a pool-sized array
    and are neither an argument nor the update in place: a scatter, a
    dynamic-update-slice, a fusion whose computation holds one, or the loop
    that carries one."""
    size = math.prod(pool_shape)
    in_place = re.compile(r" (scatter|dynamic-update-slice)\(")
    updates = {m.group(1) for m in re.finditer(
        r"^%([\w.\-]+) \([^\n]*\{\n((?:[^}][^\n]*\n)*?)\}", text, re.M)
        if in_place.search(m.group(2))}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\][^ ]* "
                     r"([\w\-]+)\(", line)
        if not m or math.prod(int(x) for x in m.group(2).split(",")) != size:
            continue
        op = m.group(3)
        if op in ("parameter", "bitcast", "scatter", "dynamic-update-slice",
                  "get-tuple-element", "while", "tuple"):
            continue
        calls = re.search(r"calls=%([\w.\-]+)", line)
        if op == "fusion" and calls and calls.group(1) in updates:
            continue
        found.append(f"{m.group(1)}:{op}")
    return found


def compiled_report(fn, specs, pool_shape) -> dict:
    compiled = fn.lower(*specs).compile()
    mem = compiled.memory_analysis()
    return {"temp_mb": mem.temp_size_in_bytes / 1e6,
            "alias_mb": mem.alias_size_in_bytes / 1e6,
            "pool_copies": pool_copies(compiled.as_text(), pool_shape)}


def timed_ms(fn, pools, rest, reps: int) -> tuple:
    """(median over three sets of the milliseconds one execution takes, the
    pools as the last left them): ``reps`` dispatched back to back, each given
    the pools the last returned (they are donated), the last one waited for."""
    for _ in range(2):  # compile, then once warm
        pools = jax.block_until_ready(fn(*pools, *rest))
    sets = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(reps):
            pools = fn(*pools, *rest)
        jax.block_until_ready(pools)
        sets.append((time.perf_counter() - start) / reps * 1e3)
    return statistics.median(sets), pools


def reachable(case, pools, length):
    """The rows of the call's own seat that a read can reach, of each pool."""
    from accelerate_tpu.ops.attention import pool_heads_first

    hkv, d, _, ctx, ring, _ = case
    per = (ring or ctx) // BLOCK
    first = 1 + SLOT * per
    out = []
    for pool in pools:
        own = pool[first:first + per]
        if hkv and pool_heads_first(hkv, d):
            own = jnp.swapaxes(own, 1, 2)
        out.append(own.reshape(per * BLOCK, -1)[:min(length, ring or length)])
    return out


def run_case(tag, name, case, widths, shares, reps, mode, seed):
    """``mode``: "chip" (times), "tiny" (equality, no time), "aot" (compiled
    text for a described v5e alone)."""
    hkv, d, slots, ctx, ring, cell = case
    forms = forms_of(case)
    lines = []
    for width in widths:
        pool_shape, rows_shape, _ = shapes_of(case, width)
        n_pools = 2 if hkv else 1
        dtype = jnp.bfloat16
        if mode == "aot":
            from jax.experimental import topologies
            from jax.sharding import SingleDeviceSharding

            chip = SingleDeviceSharding(topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2").devices[0])
            specs = specs_of(case, width, chip)
            for form, fn in forms.items():
                rep = compiled_report(fn, specs, pool_shape)
                print(f"{tag} {name} S {width} {form}: temporaries "
                      f"{rep['temp_mb']:.2f} MB, aliased {rep['alias_mb']:.1f} MB, "
                      f"pool copies {rep['pool_copies'] or 'none'}", flush=True)
                lines.append({"case": name, "width": width, "form": form, **rep})
            continue
        ks = jax.random.split(jax.random.PRNGKey(seed), 2 * n_pools)
        rows = [jax.random.normal(k, rows_shape, dtype) for k in ks[:n_pools]]
        per = ctx // BLOCK
        table = (1 + SLOT * per + jnp.arange(per, dtype=jnp.int32))[None]
        for share in shares:
            length = max(1, int(round(width * share)))
            lens = jnp.asarray([length], jnp.int32)
            line = {"case": name, "cell": cell, "width": width, "length": length}
            kept = {}
            for form, fn in forms.items():
                pools = [jax.random.normal(k, pool_shape, dtype)
                         for k in ks[n_pools:]]
                if mode == "chip":
                    ms, pools = timed_ms(fn, pools, (*rows, table, lens), reps)
                    line[form + "_ms"] = ms
                    line[form + "_ns_a_row"] = ms * 1e6 / width
                else:
                    pools = fn(*pools, *rows, table, lens)
                kept[form] = reachable(case, pools, length)
            for form, got in kept.items():
                for a, b in zip(got, kept["row"]):
                    assert bool(jnp.array_equal(a, b)), (name, width, length, form)
            print(f"{tag} {name} S {width} L {length}: " + "  ".join(
                f"{form} " + (f"{line[form + '_ms']:.3f} ms "
                              f"{line[form + '_ns_a_row']:.1f} ns/row"
                              if mode == "chip" else "equal")
                for form in forms), flush=True)
            lines.append(line)
    if mode == "chip":
        pool_shape = shapes_of(case, widths[-1])[0]
        specs = specs_of(case, widths[-1])
        for form, fn in forms.items():
            rep = compiled_report(fn, specs, pool_shape)
            print(f"{tag} {name} S {widths[-1]} {form} compiled: temporaries "
                  f"{rep['temp_mb']:.2f} MB, pool copies "
                  f"{rep['pool_copies'] or 'none'}", flush=True)
            lines.append({"case": name, "width": widths[-1], "form": form, **rep})
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--widths", default="1024,4096,8192,16384")
    ap.add_argument("--shares", default="1,0.7",
                    help="real length over the bucket's width")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes for a CPU rehearsal: equality, no time")
    ap.add_argument("--aot", action="store_true",
                    help="compile the real shapes for a described v5e: the "
                    "compiled text's pool copies and temporaries, no time")
    args = ap.parse_args()

    dev = jax.devices()[0]
    tag = f"[{dev.platform} {dev.device_kind} x{jax.device_count()}]"
    mode = "aot" if args.aot else "tiny" if args.tiny else "chip"
    if mode == "chip" and dev.platform != "tpu":
        print(f"{tag} no TPU: a time comes from a chip alone (--tiny and "
              "--aot rehearse)", file=sys.stderr)
        return 2
    if mode == "aot":
        tag = "[aot v5e:2x2, not run]"
    table = TINY if mode == "tiny" else CASES
    widths = ([16, 32, 64] if mode == "tiny"
              else [int(w) for w in args.widths.split(",")])
    shares = [float(s) for s in args.shares.split(",")]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": jax.device_count()}, "mode": mode, "lines": []}
    for name in args.cases.split(","):
        out["lines"] += run_case(tag, name, table[name], widths, shares,
                                 args.reps, mode, args.seed)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
