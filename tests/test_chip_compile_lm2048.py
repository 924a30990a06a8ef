"""``lm2048``'s two paged programs compiled for a described v5e, the
sampler alone at a decode dispatch's size, and what warm-up compiles
(``tests/_chip_compile_common.py`` says how).
"""
import jax
import jax.numpy as jnp
import pytest

from _chip_compile_common import (I32, _lm_program, chip,  # noqa: F401
                                  compiled_mode,
                                  paged_program_leaves_the_pool_in_place,
                                  paged_programs_hand_the_kernel_its_blocks)

pytestmark = pytest.mark.quick


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_lm_program], ids=["lm2048"])
def test_paged_program_leaves_the_pool_in_place(chip, compiled_mode,
                                                build, kind):
    paged_program_leaves_the_pool_in_place(chip, build, kind)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_lm_program], ids=["lm2048"])
def test_paged_programs_hand_the_kernel_its_blocks(chip, compiled_mode,
                                                   build, kind):
    paged_programs_hand_the_kernel_its_blocks(chip, build, kind)


def test_sampler_sorts_and_draws_only_in_a_conditional(chip):
    """The sampler at ``lfm2-24b-a2b``'s decode dispatch, 128 rows of
    65,536 logits, compiled for the described v5e: the vocabulary sort
    is a branch computation of a ``conditional`` (of two, nested), and
    ``ENTRY`` produces nothing of the logits' shape: no sort, no random
    bits, no Gumbel, so a greedy dispatch runs an argmax and a key
    split."""
    import re
    from mxnet_tpu.serving.program_store import sample_tokens

    rows, vocab = 128, 65536
    text = jax.jit(sample_tokens).lower(
        chip((rows, vocab)), chip((rows, 2), jnp.uint32), chip((rows,)),
        chip((rows,), I32)).compile().as_text()
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    branches = set(re.findall(
        r"%([^\s,}]+)", " ".join(re.findall(
            r"branch_computations=\{([^}]*)\}", text))))
    sorts = [n for n, body in bodies.items()
             if any(re.search(r" sort\(", ln) for ln in body)]
    assert sorts and set(sorts) <= branches, (sorts, branches)
    assert sum(" conditional(" in ln for ln in bodies["ENTRY"]) == 1
    assert sum(" conditional(" in ln for body in bodies.values()
               for ln in body) == 2
    inst = re.compile(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(")
    wide = [ln.strip()[:120] for ln in bodies["ENTRY"]
            for hit in [inst.match(ln)]
            if hit and "[%d,%d]" % (rows, vocab) in hit.group(1)
            and hit.group(2) not in ("parameter", "copy-start",
                                     "copy-done", "tuple")]
    assert not wide, "\n".join(wide)


def test_warmup_compiles_the_two_programs_a_burst_dispatches():
    """``warmup()`` compiles exactly the decode program and the
    compacted chunk program of each slot bucket (no slot-wide chunk
    program), and a burst that puts every slot in its prompt compiles
    nothing after it: the benchmark's drivers make
    ``store_compiles_after_warmup == 0`` a condition of a run.  On the
    CPU, at rehearsal size: what is counted is programs, not time."""
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    from mxnet_tpu.serving import GenerationEngine, ModelRegistry

    spec = lm_spec(num_layers=2, num_hidden=32, num_heads=4,
                   vocab_size=50)
    for sample, chunk_kind, rows in (
            ("graph", "paged_chunk_sample", 16),
            ("host", "paged_step", 4)):
        reg = ModelRegistry()
        store = reg.add_generative_model(
            "m", random_params(spec, seed=3), spec, batch_buckets=(16,),
            prompt_buckets=(8,), kv_block=8, kv_max=40, paged=True,
            prefill_chunk=4, sample=sample, warmup=False)
        decode_kind = "paged_step_sample" if sample == "graph" \
            else "paged_step"
        assert store.chunk_rows(16) == 4
        assert set(store.warmup()) == {(decode_kind, 16, 1),
                                       (chunk_kind, rows, 4)}
        warm = store.stats()
        assert warm["compiles"] == 2
        assert [tuple(r) for r in warm["programs_resident"]] == sorted(
            [(decode_kind, 16, 1), (chunk_kind, rows, 4)])
        eng = GenerationEngine(reg)
        try:
            futs = [eng.submit("m", [i, 7, 3, 19, 4, 1, 2, 3, 9],
                               max_tokens=3) for i in range(24)]
            assert all(len(f.result(300).tokens) == 3 for f in futs)
            stats = eng.stats()
        finally:
            eng.close()
        assert stats["prefill_rows_deferred"] > 0
        assert store.stats()["compiles"] == 2, sample
