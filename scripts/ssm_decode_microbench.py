#!/usr/bin/env python
"""Time the state-space decode step alone on the chip, at the
granite-4.0-h-small-ep2 cell's shapes: segments of five and four Mamba-2
layers, 64 slots, a state of 128 heads x 64 x 128 float32 a slot and layer
(2.4 GB in all), each segment one scan over its layers with the states as
the carry, as the decode program has it. Three ways to take the step:

    xla        ``_ssm_step`` between a ``dynamic_index_in_dim`` and a
               ``dynamic_update_index_in_dim``, a ``where`` over all slots,
               on the layout before PR 36
    stored.tH  the same walk on the layout before PR 36, (layers, slots,
               heads, head size, state size), H heads a block: ``dt x`` and
               the decay are turned to columns a head, the readout a sum
               over lanes
    minor.tC   the kernel (``nn/ops/ssm_decode.py``) on the stored layout
               (layers, slots, state size, heads x head size), C columns a
               block: ``dt x`` and the decay are lane vectors, B and C
               columns, the readout a sum over sublanes

under loads of 0, 16, 42 and 64 live slots.

    chiprun -- python scripts/ssm_decode_microbench.py \
        --out chiprun_out/ssm_microbench.json

One JSON object: per variant and load the milliseconds of nine layers, the
share of the HBM roofline of the LIVE slots' bytes (one read and one
write), and the largest gap to ``xla`` in the readout of the live rows and
in the sums of each slot and layer's new state. Needs the chip (``--cpu``
is a rehearsal at a tiny size under the Pallas interpreter: no timing means
anything there).
"""

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HBM_BYTES_PER_S = 819e9  # one TPU v5e, Google Cloud documentation


# -- the walk on (layers, slots, heads, head size, state size) ----------------
def _stored_kernel(slot_ref, live_ref, layer_ref, dtx_ref, dec_ref, b_ref,
                   c_ref, h_ref, hc_ref, o_ref, *, tile, p):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    del slot_ref, layer_ref

    @pl.when(live_ref[0] > 0)
    def _step():
        b_row, c_row = b_ref[...], c_ref[...]                 # (1, N)

        def head(j, carry):
            h = h_ref[j]                                      # (P, N)
            # the per-channel scalars arrive channel-minor: to columns
            dtx = dtx_ref[pl.ds(j, 1), :].reshape(p, 1)
            decay = dec_ref[pl.ds(j, 1), :].reshape(p, 1)
            hc_ref[pl.ds(j, 1), :] = jnp.sum(
                h * c_row, axis=1, keepdims=True).reshape(1, p)
            o_ref[j] = h * decay + dtx * b_row
            return carry

        jax.lax.fori_loop(0, tile, head, 0)

    @pl.when(live_ref[0] == 0)
    def _nothing_live():
        o_ref[...] = h_ref[...]


def stored_step(states, layer, table, dtx, decay, bvec, cvec, *, tile,
                interpret=False):
    """``ssm_decode_step`` on states (layers, slots, H, P, N), the layout
    before PR 36: dtx and decay (slots, H x P), bvec and cvec (slots, 1, N)
    (one group); ``tile`` heads a block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _layers, n_slots, heads, p, n = states.shape
    tiles = heads // tile
    slot_of, n_live, live = table
    n_steps = jnp.maximum(n_live[0], 1) * tiles

    def row(i, slot_ref, live_ref, layer_ref):
        return (slot_ref[i // tiles], i % tiles, 0)

    def group(i, slot_ref, live_ref, layer_ref):
        return (slot_ref[i // tiles], 0, 0)

    def block(i, slot_ref, live_ref, layer_ref):
        return (layer_ref[0], slot_ref[i // tiles], i % tiles, 0, 0)

    hc, new = pl.pallas_call(
        functools.partial(_stored_kernel, tile=tile, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_steps,),
            in_specs=[pl.BlockSpec((None, tile, p), row),
                      pl.BlockSpec((None, tile, p), row),
                      pl.BlockSpec((None, 1, n), group),
                      pl.BlockSpec((None, 1, n), group),
                      pl.BlockSpec((None, None, tile, p, n), block)],
            out_specs=[pl.BlockSpec((None, tile, p), row),
                       pl.BlockSpec((None, None, tile, p, n), block)]),
        out_shape=[jax.ShapeDtypeStruct((n_slots, heads, p), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="ssm_decode_step_stored",
    )(slot_of, n_live, jnp.reshape(layer, (1,)).astype(jnp.int32),
      dtx.reshape(n_slots, heads, p), decay.reshape(n_slots, heads, p),
      bvec, cvec, states)
    return jnp.where(live[:, None], hc.reshape(dtx.shape), 0.0), new


# -- the three ways, a segment's scan each -------------------------------------
def xla_step(states, layer, table, dtx, decay, bvec, cvec):
    """The sequence ``_ssm_mixer`` had around ``_ssm_step`` before PR 36, on the
    kernel's operands (a = -1, dt = -log(decay) and x = dtx / dt give
    ``_ssm_step`` the same decay and input, to rounding), on the layout
    the cache had then."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.decoder_lm import _ssm_step

    _layers, b, heads, p, n = states.shape
    dtx, decay = dtx.reshape(b, heads, p), decay.reshape(b, heads, p)
    old = jax.lax.dynamic_index_in_dim(
        states, layer, 0, keepdims=False).reshape(b, 1, heads, p, n)
    dt = -jnp.log(decay[:, None, :, 0])
    y, h = _ssm_step(old, dtx[:, None] / dt[..., None], dt,
                     -jnp.ones((1, heads), jnp.float32), bvec, cvec)
    h = jnp.where(table[2][:, None, None, None, None], h, old)
    states = jax.lax.dynamic_update_index_in_dim(
        states, h.reshape(b, heads, p, n), layer, 0)
    y = y[:, 0].reshape(b, heads * p)
    return jnp.where(table[2][:, None], y, 0.0), states


def nine_layers(step, readout):
    """step(states, layer, table, dtx, decay, b, c) -> (rows, states),
    scanned over each segment's layers with the states as the carry;
    ``readout(rows, decay, dtx, b, c)`` makes the rows comparable between
    variants. Returns (the layers' rows summed, the segments' states)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def run(segments, active, small):
        from deeplearning4j_tpu.nn.ops.ssm_decode import live_table

        table = live_table(active)
        total, out = 0.0, []
        for states, xs in zip(segments, small):
            def body(carry, x):
                states, acc = carry
                layer, dtx, decay, b, c = x
                rows, states = step(states, layer, table, dtx, decay, b, c)
                return (states, acc + readout(rows, decay, dtx, b, c)), None

            layers = states.shape[0]
            (states, acc), _ = jax.lax.scan(
                body, (states, jnp.zeros(xs[0].shape[1:], jnp.float32)),
                (jnp.arange(layers, dtype=jnp.int32), *xs))
            total, out = total + acc, out + [states]
        return total, out

    return run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--head-tiles", default="16,32,64")
    ap.add_argument("--column-tiles", default="1024,2048,4096")
    ap.add_argument("--loads", default="0,16,42,64")
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.ops import ssm_decode as sd

    if args.cpu:
        depths, n_slots, heads, p, n = (2, 1), 6, 8, 16, 16
        head_tiles, column_tiles, loads = [4, 8], [64, 128], [0, 2, 4, 6]
    else:
        if jax.default_backend() != "tpu":
            raise SystemExit("no TPU here: times from another backend say "
                             "nothing (--cpu rehearses the control flow)")
        depths, n_slots, heads, p, n = (5, 4), 64, 128, 64, 128
        head_tiles = [int(t) for t in args.head_tiles.split(",")]
        column_tiles = [int(t) for t in args.column_tiles.split(",")]
        loads = [int(v) for v in args.loads.split(",")]
    f32 = jnp.float32
    rng = np.random.default_rng(0)

    def small(layers):
        """A segment's per-layer operands: dt x, the decay over a head's
        channels, B, C."""
        decay = np.repeat(rng.uniform(0.6, 0.99, (layers, n_slots, heads)),
                          p, axis=-1)
        return tuple(jnp.asarray(v, f32) for v in (
            rng.standard_normal((layers, n_slots, heads * p)), decay,
            rng.standard_normal((layers, n_slots, 1, n)),
            rng.standard_normal((layers, n_slots, 1, n))))

    operands = [small(layers) for layers in depths]

    def fresh(minor):
        """The segments' states from one seed, in either layout."""
        out = []
        for i, layers in enumerate(depths):
            s = jax.random.normal(jax.random.PRNGKey(i),
                                  (layers, n_slots, heads, p, n), f32)
            if minor:
                s = s.transpose(0, 1, 4, 2, 3).reshape(
                    layers, n_slots, n, heads * p)
            out.append(s)
        return out

    @jax.jit
    def digest(segments):
        """(layers, slots) sums of each state and of its magnitudes: the
        same in either layout, small enough to fetch."""
        return [jnp.stack([jnp.sum(s, axis=(2, 3)),
                           jnp.sum(jnp.abs(s), axis=(2, 3))])
                for s in (s.reshape(*s.shape[:2], -1, s.shape[-1])
                          for s in segments)]

    def finished(rows, decay, dtx, b, c):  # y of _ssm_step from the kernel's hc
        return decay * rows + dtx * jnp.sum(b * c, axis=-1)

    variants = {"xla": (xla_step, lambda rows, *_: rows, False)}
    for t in head_tiles:
        variants[f"stored.t{t}"] = (functools.partial(
            stored_step, tile=t, interpret=args.cpu), finished, False)
    for t in column_tiles:
        variants[f"minor.t{t}"] = (functools.partial(
            sd.ssm_decode_step, tile=t, interpret=args.cpu), finished, True)

    live_bytes = 2 * sum(depths) * heads * p * n * 4   # a slot: read + write
    out = {"device": jax.devices()[0].device_kind,
           "shapes": {"depths": depths, "slots": n_slots, "heads": heads,
                      "head_size": p, "state_size": n}}
    want = {}
    for name, (step, readout, minor) in variants.items():
        run = nine_layers(step, readout)
        for load in loads:
            active = np.zeros((n_slots,), bool)
            active[np.random.default_rng(load).choice(
                n_slots, size=load, replace=False)] = True
            act = jnp.asarray(active)
            try:
                t0 = time.perf_counter()
                total, segs = run(fresh(minor), act, operands)
                total = np.asarray(total)
                first_s = time.perf_counter() - t0
                held = [np.asarray(d) for d in digest(segs)]
                if name == "xla":
                    want[load] = (total, held)
                gap_y = (float(np.abs(total - want[load][0])[active].max())
                         if load else 0.0)
                gap_h = max(float(np.abs(g - w).max())
                            for g, w in zip(held, want[load][1]))
                t0 = time.perf_counter()
                for _ in range(args.repeats):
                    res, segs = run(segs, act, operands)
                res.block_until_ready()
                ms = 1e3 * (time.perf_counter() - t0) / args.repeats
                del segs
                got = {"ms_nine_layers": ms, "first_call_s": first_s,
                       "live_hbm_share_pct": 100 * load * live_bytes
                       / HBM_BYTES_PER_S / (ms / 1e3),
                       "max_gap_y_live_rows": gap_y,
                       "max_gap_state_sums": gap_h}
            except Exception as e:  # noqa: BLE001 — a refused variant is a reading
                got = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            out.setdefault(name, {})[str(load)] = got
            print(name, load, json.dumps(got), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
