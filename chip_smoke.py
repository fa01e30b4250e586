#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip.  Not a benchmark.

    python3 chip_smoke.py

Serves smollm-135m at its published widths (30 layers, d_model 576,
9 query / 3 KV heads, head_dim 64, vocab 49152, bf16), with random
weights from a fixed seed, through ``ServeEngine(..., reduced=False)``:
the paged KV pool read by the fused Pallas decode kernel, chunked
prefill through the Pallas flash kernel, and TUNED plans resolved into
an in-memory tuning cache.  One process, no children.

Phases, in order; any failure exits non-zero:

  1. device: JAX must find a TPU;
  2. bf16 probe: one request alone; its first-step (prefill) logits and
     every decode step's logits are compared with the model's own
     forward pass run under ``ops.force("ref")``;
  3. bf16 traffic: 8 seeded requests (prompts of 16-200 tokens, 16 new
     tokens each); each must return 16 tokens, and each token must be a
     near-argmax of the reference logits at its position;
  4. int8 pool: the probe again, its decode logits compared with the
     bf16 pool's while the two token streams agree, then the traffic;
  5. kernels: the compiled decode and chunked-prefill steps must contain
     ``tpu_custom_call`` (the Pallas kernels ran, not a reference).

The last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
SEED = 0
SLOTS = 8
MAX_LEN = 512
N_REQUESTS = 8
PROMPT_LENS = (16, 200)
NEW_TOKENS = 16
REF_LEN = 256          # reference batch width: >= longest prompt + answer

#: bf16 pool vs the reference forward, as a fraction of the largest
#: |logit| at that position.  Both run the same bf16 weights; they differ
#: in the attention kernels and in where bf16 rounding (8 significant
#: bits, relative spacing 2^-8) happens in each of 30 layers, so a few
#: percent of the logit scale separates them; a wrong cache read or
#: mask moves logits by the whole scale.
BF16_TOL = 0.05
#: int8 pool vs bf16 pool (and vs the reference), same units.  Symmetric
#: per-(block, head) int8 codes round each K/V element by up to 1/254 of
#: the block's largest |value|; that error enters every layer's
#: attention, so the int8 pool is allowed twice the bf16 margin.
INT8_TOL = 0.10


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class StepRecorder:
    """Stands in for one of an engine's jitted steps: forwards every call
    and keeps the first call's arguments (to compile the step again for
    its HLO) and, while ``active``, each call's logits together with the
    engine's live slot map."""

    def __init__(self, engine, attr: str):
        self.engine = engine
        self.step = getattr(engine, attr)
        self.first = None
        self.active = True
        self.calls: list[tuple[dict, object]] = []
        setattr(engine, attr, self)

    def __call__(self, *args, **kwargs):
        out = self.step(*args, **kwargs)
        if self.first is None:
            self.first = (args, kwargs)
        if self.active:
            live = {r.rid: s for s, r in
                    self.engine.scheduler.live_by_slot().items()}
            self.calls.append((live, out[0]))
        return out

    def compiled_text(self) -> str:
        args, kwargs = self.first
        return self.step.lower(*args, **kwargs).compile().as_text()


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """max |got - ref| over max |ref|: logit error in units of scale."""
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def make_prompts(vocab: int) -> list[list[int]]:
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    lens[0] = PROMPT_LENS[1]              # the probe: the longest prompt
    return [rng.integers(0, vocab, int(n)).tolist() for n in lens]


def make_reference(model):
    """The model's own forward pass under ``ops.force("ref")`` (no Pallas
    kernel, whole sequences, no KV cache).  The returned function takes
    sequences, right-pads them to ``REF_LEN`` and returns, per sequence,
    the float32 logits at positions ``want[i]``: (n_positions, vocab)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])

    def reference(params, seqs, want):
        toks = np.zeros((len(seqs), REF_LEN), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        with ops.force("ref"):
            logits = fwd(params, jnp.asarray(toks))
        return [np.asarray(logits[i, np.asarray(w)], np.float32)
                for i, w in enumerate(want)]

    return reference


def probe(engine, prompt):
    """Serve one request alone; return its tokens, its first-step logits
    and its decode-step logits (one row per decode tick)."""
    chunk = StepRecorder(engine, "_chunk_step")
    decode = StepRecorder(engine, "_decode")
    req = engine.submit(prompt, max_new_tokens=NEW_TOKENS)
    engine.run()
    chunk.active = decode.active = False
    first = np.asarray(chunk.calls[-1][1][0], np.float32)   # (C, V)
    n_last = (len(prompt) - 1) % first.shape[0]
    steps = [np.asarray(lg[live[req.rid], 0], np.float32)
             for live, lg in decode.calls if req.rid in live]
    return list(req.generated), first[n_last], steps, chunk, decode


def check_probe(tag, prompt, gen, first, steps, ref, tol):
    """First-step and decode logits against the reference rows
    ``ref[k]`` (position ``len(prompt) - 1 + k``)."""
    if len(gen) != NEW_TOKENS or len(steps) != NEW_TOKENS - 1:
        raise AssertionError(f"{tag}: {len(gen)} tokens, {len(steps)} "
                             f"decode ticks (want {NEW_TOKENS}, "
                             f"{NEW_TOKENS - 1})")
    if int(np.argmax(first)) != gen[0]:
        raise AssertionError(f"{tag}: served token {gen[0]} is not the "
                             f"argmax of the step's own logits")
    errs = [rel_err(first, ref[0])]
    errs += [rel_err(s, ref[k + 1]) for k, s in enumerate(steps)]
    log(f"{tag}: logit error vs reference, first step {errs[0]:.4f}, "
        f"decode max {max(errs[1:]):.4f} (tolerance {tol})")
    if max(errs) > tol:
        raise AssertionError(f"{tag}: logit error {max(errs):.4f} > {tol}")


def check_traffic(tag, engine, prompts, reference, tol):
    """Serve every prompt; each request must return NEW_TOKENS tokens,
    each a near-argmax of the reference logits at its position."""
    engine.reset()
    reqs = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    rep = engine.run()
    short = [(r.rid, len(r.generated)) for r in reqs
             if len(r.generated) != NEW_TOKENS]
    if short or len(rep.completed) != len(prompts):
        raise AssertionError(f"{tag}: {len(rep.completed)}/{len(prompts)} "
                             f"completed; wrong token counts {short}")
    seqs = [r.prompt + r.generated[:-1] for r in reqs]
    want = [range(r.prompt_len - 1, r.prompt_len - 1 + NEW_TOKENS)
            for r in reqs]
    refs = reference(engine.params, seqs, want)
    worst = 0.0
    for r, ref in zip(reqs, refs):
        top = ref.max(axis=1)
        chosen = ref[np.arange(NEW_TOKENS), r.generated]
        gap = (top - chosen) / np.abs(ref).max(axis=1)
        worst = max(worst, float(gap.max()))
    log(f"{tag}: {len(reqs)} requests x {NEW_TOKENS} tokens; worst served "
        f"token's reference gap to the argmax {worst:.4f} (tolerance {tol})")
    if worst > tol:
        raise AssertionError(f"{tag}: a served token trails the reference "
                             f"argmax by {worst:.4f} > {tol}")
    s = rep.summary
    log(f"{tag} (smoke run, compiles included, not a benchmark): "
        f"ttft p50 {s.ttft_p50_s * 1e3:.1f} ms, {s.tokens_per_s:.1f} tok/s, "
        f"compiles decode={rep.compiled_decode_shapes} "
        f"chunked-prefill={rep.compiled_chunk_shapes}, "
        f"pool growths={rep.pool_growths}")
    return rep


def serve_checks(arch, *, reduced: bool = False) -> dict:
    """Phases 2-4; returns the step recorders of both pools (phase 5)."""
    from repro.serve import ServeEngine
    from repro.tuner import TuningCache

    def engine(kv_dtype, params=None):
        return ServeEngine(arch, slots=SLOTS, max_len=MAX_LEN,
                           reduced=reduced, policy="tuned",
                           tuning_cache=TuningCache(path=None),
                           kv_dtype=kv_dtype, params=params)

    eng = engine("fp32")            # "fp32" = the model's dtype (bf16)
    cfg = eng.cfg
    reference = make_reference(eng.model)
    log(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype}; slots={SLOTS} "
        f"max_len={MAX_LEN}")
    prompts = make_prompts(cfg.vocab_size)
    p0 = prompts[0]
    pos = [range(len(p0) - 1, len(p0) - 1 + NEW_TOKENS)]

    gen, first, steps, chunk, decode = probe(eng, p0)
    ref = reference(eng.params, [p0 + gen[:-1]], pos)[0]
    check_probe("bf16 probe", p0, gen, first, steps, ref, BF16_TOL)
    check_traffic("bf16 traffic", eng, prompts, reference, BF16_TOL)

    eng8 = engine("int8", params=eng.params)
    gen8, first8, steps8, chunk8, decode8 = probe(eng8, p0)
    ref8 = reference(eng.params, [p0 + gen8[:-1]], pos)[0]
    check_probe("int8 probe", p0, gen8, first8, steps8, ref8, INT8_TOL)
    # decode tick k consumed token k: compare while the streams agree
    same = next((k for k in range(NEW_TOKENS) if gen8[k] != gen[k]),
                NEW_TOKENS)
    errs = [rel_err(steps8[k], steps[k]) for k in range(min(same, len(steps)))]
    if not errs:
        raise AssertionError("int8 pool: first token differs from bf16; "
                             "no decode step to compare")
    log(f"int8 vs bf16 pool: decode logit error max {max(errs):.4f} over "
        f"{len(errs)} steps with equal history (tolerance {INT8_TOL}); "
        f"streams agree for {same}/{NEW_TOKENS} tokens")
    if max(errs) > INT8_TOL:
        raise AssertionError(f"int8 pool: logit error {max(errs):.4f} vs "
                             f"bf16 > {INT8_TOL}")
    check_traffic("int8 traffic", eng8, prompts, reference, INT8_TOL)
    return {"bf16 decode": decode, "bf16 chunked prefill": chunk,
            "int8 decode": decode8, "int8 chunked prefill": chunk8}


def kernel_checks(recorders: dict) -> None:
    """Phase 5: the compiled steps hold the Pallas kernels."""
    for name, rec in recorders.items():
        found = "tpu_custom_call" in rec.compiled_text()
        log(f"{name} step: tpu_custom_call {'present' if found else 'MISSING'}")
        if not found:
            raise AssertionError(f"{name} step compiled without a Pallas "
                                 f"kernel")


def main() -> int:
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devs)} ({dev.platform})")

    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    kernel_checks(serve_checks(ARCH))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
