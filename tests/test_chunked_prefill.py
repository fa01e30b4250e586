"""Chunked-prefill invariants (``ServeEngine(prefill_chunk=...)``).

The engine can prefill prompts in fixed-width chunks interleaved with
decode ticks instead of one whole-prompt pass.  The guarantees:

  * dense/MoE chunking is token-EXACT against the whole-prompt path —
    the chunk step writes the same cache and produces the same
    final-position logits (causal masking hides the padded tail, so no
    validity mask is needed);
  * the chunk compile set is bounded by the (chunk, cache_len, tiles)
    lattice, NOT by prompt lengths — in particular the ssm family's
    length-free row cache compiles exactly ONE chunk step no matter how
    many distinct exact prompt lengths arrive (the compile-set leak the
    whole-prompt exact-length path has);
  * outputs are chunk-size invariant: any chunk width produces the same
    tokens;
  * interleaving holds: with a long prompt in flight, decode ticks of
    already-seated requests keep landing between its chunks.
"""

import dataclasses

import pytest

from repro.configs.base import get_config
from repro.tuner import TuningCache

PROMPTS = [[7, 3, 99], [11, 5, 2, 42, 17, 101, 9], [250, 1],
           [33, 44, 55, 66]]
MAX_NEW = 4


@pytest.fixture(scope="module")
def dense_setup():
    import jax

    from repro.models import build_model

    cfg = dataclasses.replace(get_config("smollm-135m").reduced(),
                              dtype="float32")
    return cfg, build_model(cfg).init(jax.random.key(0))


@pytest.fixture(scope="module")
def ssm_setup():
    import jax

    from repro.models import build_model

    cfg = dataclasses.replace(get_config("mamba2-1.3b").reduced(),
                              dtype="float32")
    return cfg, build_model(cfg).init(jax.random.key(0))


def _run(cfg, params, prompts=PROMPTS, **kw):
    from repro.serve import ServeEngine

    eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                      tuning_cache=TuningCache(path=None), **kw)
    reqs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    rep = eng.run()
    return [rep.outputs[r.rid] for r in reqs], rep


class TestDenseExactness:
    def test_pallas_flash_chunk_step_matches_reference_sweep(
            self, dense_setup):
        """Under the kernel path (interpret mode) the chunk step runs the
        Pallas flash kernel with the chunk's traced start as its query
        offset: chunk by chunk, logits and cache match the reference
        tile sweep."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.kernels import ops
        from repro.models import build_model

        cfg, params = dense_setup
        model = build_model(cfg)
        toks = jnp.asarray(np.arange(3, 27, dtype=np.int32)[None])
        out = {}
        for mode in ("ref", "interpret"):
            with ops.force(mode):
                step = jax.jit(lambda p, c, t, n: model.prefill_chunk(
                    p, c, t, n, prefill_tiles=(8, 128)))
                cache, logits = model.init_cache(1, 32), []
                for s in range(0, 24, 8):
                    lg, cache = step(params, cache, toks[:, s:s + 8],
                                     jnp.int32(8))
                    logits.append(np.asarray(lg))
            out[mode] = np.concatenate(logits, 1), np.asarray(cache["k"])
        np.testing.assert_allclose(out["interpret"][0], out["ref"][0],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out["interpret"][1], out["ref"][1],
                                   rtol=1e-6, atol=1e-6)

    def test_chunked_matches_whole_prefill(self, dense_setup):
        cfg, params = dense_setup
        whole, _ = _run(cfg, params, prefill_chunk=None)
        for chunk in (2, 3):
            chunked, rep = _run(cfg, params, prefill_chunk=chunk)
            assert chunked == whole, f"chunk={chunk} changed tokens"
            assert rep.summary.n_completed == len(PROMPTS)

    def test_auto_chunk_uses_tuned_tile(self, dense_setup):
        cfg, params = dense_setup
        whole, _ = _run(cfg, params, prefill_chunk=None)
        chunked, rep = _run(cfg, params, prefill_chunk="auto")
        assert chunked == whole
        # auto = the prompt bucket's tuned block_q: every chunk shape in
        # the set must carry the tiles it was derived from
        assert rep.compiled_chunk_shapes >= 1

    def test_chunk_compile_set_is_lattice_bounded(self, dense_setup):
        """4 ragged prompts through one chunk width on one prompt
        bucket: exactly one compiled chunk shape."""
        cfg, params = dense_setup
        _, rep = _run(cfg, params, prefill_chunk=2)
        assert rep.compiled_chunk_shapes == 1
        assert rep.compiled_decode_shapes == 1

    def test_exact_mode_clamps_auto_chunk_to_prompt(self, dense_setup):
        """mode="exact" prompt buckets are the RAW prompt length while
        the auto chunk width (the tuned block_q) is padded up to a tile
        multiple — the chunk must clamp to the row or the chunked cache
        write overruns an exact-length cache."""
        from repro.serve import BucketSpec

        cfg, params = dense_setup
        prompts = [list(range(1, 53))]       # 52 tokens: no tile multiple
        spec = BucketSpec(min_len=32, max_len=64, mode="exact")
        whole, _ = _run(cfg, params, prompts=prompts, prefill_chunk=None,
                        spec=spec, paged=False)
        chunked, rep = _run(cfg, params, prompts=prompts, spec=spec,
                            paged=False)          # default chunking on
        assert chunked == whole
        assert rep.summary.n_completed == 1

    def test_invalid_chunk_config_rejected(self, dense_setup):
        from repro.serve import ServeEngine

        cfg, params = dense_setup
        with pytest.raises(ValueError):
            ServeEngine(cfg, slots=2, max_len=64, params=params,
                        tuning_cache=TuningCache(path=None),
                        prefill_chunk="huge")


class TestSsmCompileBound:
    def test_one_compile_across_distinct_exact_lengths(self, ssm_setup):
        """THE compile-set pin: the ssm whole-prompt path compiles one
        prefill per exact prompt length; the chunked path compiles ONE
        chunk step total — its row cache is length-free, so the compile
        key is the chunk width alone."""
        cfg, params = ssm_setup
        assert cfg.is_attention_free
        prompts = [[7, 3, 99], [11, 5, 2, 42, 17], [250, 1],
                   [33, 44, 55, 66, 77, 88], [9] * 9]   # 5 distinct lengths
        outs, rep = _run(cfg, params, prompts=prompts, prefill_chunk=4)
        assert rep.summary.n_completed == len(prompts)
        assert rep.compiled_chunk_shapes == 1
        assert rep.compiled_decode_shapes == 1      # length-free decode too
        for p, o in zip(prompts, outs):
            assert len(o) == len(p) + MAX_NEW

    def test_outputs_chunk_size_invariant(self, ssm_setup):
        """The masked scan-of-decode chunk step runs the exact per-token
        recurrence, so every chunk width produces identical tokens."""
        cfg, params = ssm_setup
        a, _ = _run(cfg, params, prefill_chunk=2)
        b, _ = _run(cfg, params, prefill_chunk=5)
        assert a == b


class TestInterleaving:
    def test_decode_proceeds_between_chunks_of_long_prompt(self,
                                                          dense_setup):
        """A long prompt admitted mid-run must NOT stall the decoding
        pool: decode ticks land between its prefill chunks, and its own
        tokens still come out exact."""
        from repro.serve import ServeEngine

        cfg, params = dense_setup
        long_prompt = list(range(1, 33))             # 16 chunks at width 2
        short = [5, 6, 7]

        whole, _ = _run(cfg, params, prompts=[short, long_prompt],
                        prefill_chunk=None)

        eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                          tuning_cache=TuningCache(path=None),
                          prefill_chunk=2)
        r1 = eng.submit(short, max_new_tokens=MAX_NEW)
        r2 = eng.submit(long_prompt, max_new_tokens=MAX_NEW)

        interleaved = {"chunks_seen": 0, "decodes_during": 0}
        orig_chunk, orig_decode = eng._prefill_tick, eng._decode_tick

        def chunk_tick():
            stepped = orig_chunk()
            if stepped and eng._prefilling.get(r2.rid):
                interleaved["chunks_seen"] += 1
            return stepped

        def decode_tick():
            if r2.rid in eng._prefilling:
                interleaved["decodes_during"] += 1
            orig_decode()

        eng._prefill_tick, eng._decode_tick = chunk_tick, decode_tick
        rep = eng.run()
        assert rep.outputs[r1.rid] == whole[0]
        assert rep.outputs[r2.rid] == whole[1]
        assert interleaved["chunks_seen"] >= 8
        # the short request decoded (all its post-first tokens) while the
        # long prompt was still mid-prefill
        assert interleaved["decodes_during"] >= MAX_NEW - 1

    def test_prefilling_rows_decode_no_tokens(self, dense_setup):
        """A still-prefilling request accrues no generated tokens from
        the interleaved decode ticks it rides along with."""
        from repro.serve import ServeEngine

        cfg, params = dense_setup
        eng = ServeEngine(cfg, slots=2, max_len=64, params=params,
                          tuning_cache=TuningCache(path=None),
                          prefill_chunk=2)
        r1 = eng.submit([5, 6, 7], max_new_tokens=MAX_NEW)
        r2 = eng.submit(list(range(1, 25)), max_new_tokens=MAX_NEW)
        rep = eng.run()
        assert len(rep.outputs[r2.rid]) == 24 + MAX_NEW
