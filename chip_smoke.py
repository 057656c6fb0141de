#!/usr/bin/env python3
"""Smoke run of QSDP training and serving on a TPU, through the normal entry
points, with the compiled Pallas kernels.

    python3 chip_smoke.py               # one chip: phases (a)-(e)
    python3 chip_smoke.py --four-chips  # four chips: sharded gpt-1.3b only

One chip:
  (a) the device JAX finds (a TPU is required; anything else fails);
  (b) every kernel of kernels/quantize.py and kernels/dequant_matmul.py on
      the gpt-350m MLP weight (1024 x 4096): wire bytes that differ from the
      jnp reference on the same chip (must be 0), and the max abs error of
      rowquant_matmul against kernels.ref;
  (c) gpt-350m at its published config (24 layers, d_model 1024, vocab
      50304), mesh (1, 1), QSDP W8G8, batch 8 x seq 1024, 20 steps at lr
      1e-3 (5 of warmup) through repro.launch.train: the loss must be finite
      and end at or below the step-0 loss, and the compiled step must hold
      Pallas kernels.  Over the 50304-token vocabulary the synthetic corpus
      moves the loss less in 5 steps than batch-to-batch noise does, so the
      run takes 20;
  (d) gpt-350m served by the ContinuousScheduler from
      serve.common.build_serve_setup / make_scheduler: 8 requests, prompts of
      128-512 tokens, 32 new tokens each, chunked prefill (64) over the paged
      KV pool (blocks of 16); every request must return its 32 tokens;
  (e) the last line: {"ok": true, "device": {...}}.

--four-chips trains gpt-1.3b (24 layers, d_model 2048) on mesh (data=4,
model=1) as baseline FSDP and as QSDP W8G8 from one seed, 3 steps each, and
prints the losses, the compiled step's collectives by dtype, the train state
held by each device, and each device's peak bytes.  The QSDP step-0 loss
must be within 1% of the baseline's, and every device must hold about a
quarter of the state.

Step times printed here are smoke-run timings, not benchmark measurements.
Any failure exits non-zero without the last line.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


class SmokeFailure(Exception):
    pass


def require(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase_device() -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"(a) device: platform={info['platform']} kind={info['kind']!r} "
          f"count={info['count']}", flush=True)
    require(info["platform"] == "tpu",
            f"no TPU: JAX found {info['platform']!r} devices")
    return info


def require_compiled_kernels() -> None:
    from repro.kernels import ops

    backend = ops.resolve_backend()
    interpret = ops._default_interpret()
    print(f"    quant backend={backend} pallas_interpret={interpret}", flush=True)
    require(backend == "pallas", f"quant backend resolved to {backend!r}, not 'pallas'")
    require(not interpret, "Pallas kernels would run in interpret mode")


def phase_kernels(rows: int = 1024, cols: int = 4096, bucket: int = 1024) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quant
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(11)
    w = 0.02 * jax.random.normal(key, (rows, cols), jnp.float32)
    qkey = jax.random.fold_in(key, 1)
    differing = 0

    def ndiff(a, b) -> int:  # elements whose bits differ
        a, b = np.asarray(a), np.asarray(b)
        return int(np.sum(a.view(np.uint8) != b.view(np.uint8)) if a.dtype == np.uint8
                   else np.sum(a.view(np.uint32) != b.view(np.uint32)))

    for bits in (8, 4):
        for mode in ("nearest", "stochastic", "shift"):
            cfg = quant.QuantConfig(bits=bits, bucket_size=bucket, mode=mode)
            qp = quant.quantize(w, cfg, qkey, backend="pallas")
            qj = quant.quantize(w, cfg, qkey, backend="jnp")
            wire_p, wire_j = quant.wire_pack(qp), quant.wire_pack(qj)
            d_wire = ndiff(wire_p, wire_j)
            d_deq = ndiff(quant.dequantize(qj, backend="pallas"),
                          quant.dequantize(qj, backend="jnp"))
            differing += d_wire + d_deq
            print(f"(b) quantize_pack W{bits} {mode:<10s}: {d_wire} of "
                  f"{wire_j.size} wire bytes differ from jnp; "
                  f"unpack_dequantize: {d_deq} of {w.size} values differ",
                  flush=True)

    buckets = w.reshape(-1, bucket)
    rand = jax.random.uniform(qkey, buckets.shape)
    quant_ref = jax.jit(ref.quantize_ref, static_argnums=(2, 3))
    for stochastic in (False, True):
        got = ops.quantize_buckets(buckets, rand, 255, stochastic)
        want = quant_ref(buckets, rand, 255, stochastic)
        d = sum(ndiff(g, r) for g, r in zip(got, want))
        d_deq = ndiff(ops.dequantize_buckets(*want), jax.jit(ref.dequantize_ref)(*want))
        differing += d + d_deq
        print(f"(b) quantize (unpacked, stochastic={stochastic}): {d} codes/affine "
              f"differ from ref; dequantize: {d_deq} values differ", flush=True)

    # rowquant_matmul: per-row affine (n_seg 1) and the QSDP wire layout's
    # segment affine (n_seg = cols / bucket), decode and prefill row counts
    codes1, s1, z1 = ops.quantize_weight_rowwise(w, 8)
    n_seg = cols // bucket
    cs, ss, zs = ref.quantize_rowwise_ref(w.reshape(rows * n_seg, bucket), 255)
    layouts = {1: (codes1, s1, z1),
               n_seg: (cs.reshape(rows, cols), ss.reshape(rows, n_seg),
                       zs.reshape(rows, n_seg))}
    for m in (8, 128):
        x = jax.random.normal(jax.random.fold_in(key, m), (m, rows), jnp.float32)
        for seg, (c, s, z) in layouts.items():
            y = ops.rowquant_matmul(x, c, s, z)
            with jax.default_matmul_precision("float32"):
                y_ref = jax.jit(ref.rowquant_matmul_ref)(x, c, s, z)
                # the MXU may round the f32 operand x * scale to bf16 (half
                # an ulp, 2^-9 relative; codes <= 255 are exact): bound each
                # output's error by 2^-8 of the sum of its terms' magnitudes
                s_full = jnp.repeat(s, cols // seg, axis=1)
                z_full = jnp.repeat(z, cols // seg, axis=1)
                mag = (jnp.abs(x) @ (c.astype(jnp.float32) * s_full)
                       + jnp.abs(x) @ jnp.abs(z_full))
            diff = jnp.abs(y - y_ref)
            err = float(jnp.max(diff))
            rel = float(jnp.max(diff / mag))
            print(f"(b) rowquant_matmul M={m} K={rows} N={cols} n_seg={seg}: "
                  f"max abs err {err:.3e} vs ref (max |y| "
                  f"{float(jnp.max(jnp.abs(y_ref))):.3e}); worst error over the "
                  f"sum of term magnitudes {rel:.3e} (bound 2^-8)", flush=True)
            require(math.isfinite(err) and rel <= 2.0 ** -8,
                    f"rowquant_matmul M={m} n_seg={seg}: error {rel} of the "
                    f"term magnitudes > 2^-8")
    require(differing == 0,
            f"{differing} wire bytes / values differ between the Pallas kernels "
            f"and the jnp reference on this chip")


def phase_train(arch: str = "gpt-350m", smoke: bool = False, batch: int = 8,
                seq: int = 1024, steps: int = 20) -> None:
    from repro.launch import train

    argv = ["--arch", arch, "--data-par", "1", "--model-par", "1",
            "--wbits", "8", "--gbits", "8", "--steps", str(steps),
            "--batch", str(batch), "--seq", str(seq), "--lr", "1e-3",
            "--warmup", "5", "--log-every", "1"] + (["--smoke"] if smoke else [])
    print(f"(c) train: {' '.join(argv)}", flush=True)
    res = train.train(train.parse_args(argv))
    losses = [r["loss"] for r in res["log"]]
    print(f"(c) compile {res['compile_s']:.1f}s; smoke-run step times (s, after "
          f"block_until_ready; not a benchmark): "
          f"{[round(r['step_s'], 3) for r in res['log']]}", flush=True)
    print(f"(c) losses: {losses}", flush=True)
    require(all(math.isfinite(v) for v in losses), f"non-finite loss: {losses}")
    require(losses[-1] <= losses[0], f"loss rose: {losses[0]} -> {losses[-1]}")
    require("tpu_custom_call" in res["compiled"].as_text(),
            "the compiled QSDP step holds no tpu_custom_call (no Pallas kernel)")


def phase_serve(arch: str = "gpt-350m", smoke: bool = False, n_requests: int = 8,
                prompt_lens: tuple = (128, 512), gen: int = 32,
                prefill_chunk: int = 64, kv_block_size: int = 16,
                seed: int = 0) -> None:
    import jax
    import numpy as np

    from repro.core.qsdp import QSDPConfig
    from repro.serve import Request, build_serve_setup, make_scheduler

    setup = build_serve_setup(arch, smoke=smoke, qsdp=QSDPConfig(weight_bits=8),
                              batch=n_requests, prompt_len=prompt_lens[1],
                              gen=gen, seed=seed, kv_block_size=kv_block_size)
    sched = make_scheduler(setup, gather_key=jax.random.PRNGKey(seed),
                           prefill_chunk=prefill_chunk)
    rng = np.random.default_rng(seed)
    vocab = setup.cfg.vocab_size
    lens = []
    for i in range(n_requests):
        lens.append(int(rng.integers(prompt_lens[0], prompt_lens[1] + 1)))
        sched.submit(Request(rid=f"req{i}",
                             prompt=rng.integers(0, vocab, size=lens[-1]).tolist(),
                             max_new_tokens=gen))
    t0 = time.time()
    done = sched.run()
    dt = time.time() - t0
    st = sched.stats()
    print(f"(d) serve {setup.cfg.name}: prompts {lens}, {len(done)} requests, "
          f"{st['tokens_generated']} tokens in {dt:.1f}s incl. compile (smoke "
          f"run, not a benchmark); {st['prefill_chunks']} prefill chunks, "
          f"{st['decode_steps']} decode steps, {st['blocks_total']} KV blocks",
          flush=True)
    require(sorted(done) == sorted(f"req{i}" for i in range(n_requests)),
            f"completed {sorted(done)}")
    for rid, c in done.items():
        toks = np.asarray(c.tokens)
        require(toks.shape == (gen,), f"{rid} returned {toks.shape[0]} tokens, not {gen}")
        require(bool(np.all((toks >= 0) & (toks < vocab))), f"{rid}: token outside vocab")


def _state_bytes_per_device(state) -> dict:
    import jax

    per = {}
    for leaf in jax.tree.leaves(state):
        for sh in leaf.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return per


def phase_four_chips(arch: str = "gpt-1.3b", smoke: bool = False, batch: int = 8,
                     seq: int = 1024, steps: int = 3) -> None:
    import jax

    from repro.launch import train
    from repro.roofline.hlo_analyzer import analyze_hlo

    require(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found {len(jax.devices())}")
    base = ["--arch", arch, "--data-par", "4", "--model-par", "1",
            "--steps", str(steps), "--batch", str(batch), "--seq", str(seq),
            "--warmup", "1", "--log-every", "1"] + (["--smoke"] if smoke else [])
    step0 = {}
    for name, extra in (("baseline-FSDP", ["--baseline"]),
                        ("QSDP-W8G8", ["--wbits", "8", "--gbits", "8"])):
        res = train.train(train.parse_args(base + extra))
        losses = [r["loss"] for r in res["log"]]
        text = res["compiled"].as_text()
        coll = analyze_hlo(text)["collectives"]["counts_by_dtype"]
        held = _state_bytes_per_device(res["state"])
        stats = [d.memory_stats() or {} for d in jax.devices()]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        ma = res["compiled"].memory_analysis()
        print(f"(4) {name}: losses {losses}", flush=True)
        print(f"(4) {name}: compile {res['compile_s']:.1f}s; smoke-run step times "
              f"(s, not a benchmark) {[round(r['step_s'], 3) for r in res['log']]}",
              flush=True)
        print(f"(4) {name}: collective instructions in the compiled step by "
              f"kind:dtype (a loop body counts once) {coll}", flush=True)
        print(f"(4) {name}: train state bytes held per device {held}; program "
              f"argument/temp bytes per device {ma.argument_size_in_bytes}/"
              f"{ma.temp_size_in_bytes}; peak_bytes_in_use per device since "
              f"start {peaks}", flush=True)
        require(all(math.isfinite(v) for v in losses), f"{name}: non-finite loss")
        total = sum(held.values())
        require(len(held) == 4 and all(abs(b / total - 0.25) < 0.05 for b in held.values()),
                f"{name}: state not split in quarters: {held}")
        step0[name] = losses[0]
        if name.startswith("QSDP"):
            require("tpu_custom_call" in text, "QSDP step holds no Pallas kernel")
            require(coll.get("all-gather:u8", 0) > 0, "QSDP step has no u8 all-gather")
        del res
        gc.collect()
    rel = abs(step0["QSDP-W8G8"] - step0["baseline-FSDP"]) / abs(step0["baseline-FSDP"])
    print(f"(4) step-0 loss: QSDP {step0['QSDP-W8G8']} vs baseline "
          f"{step0['baseline-FSDP']} (relative gap {rel:.2e})", flush=True)
    require(rel <= 0.01, f"QSDP step-0 loss {rel:.2%} from the baseline's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip path")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: FAILED: the repo's src/ is not beside this script ({e})",
              file=sys.stderr)
        return 1
    try:
        info = phase_device()
        from repro.launch.compile_cache import enable_compile_cache

        print(f"    compile cache: {enable_compile_cache()}", flush=True)
        require_compiled_kernels()
        t0 = time.time()
        if args.four_chips:
            phase_four_chips()
        else:
            phase_kernels()
            gc.collect()
            phase_train()
            gc.collect()
            phase_serve()
        print(f"    all phases passed in {time.time() - t0:.0f}s", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
