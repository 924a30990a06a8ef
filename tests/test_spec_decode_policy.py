"""Speculative decoding's policy: the ``auto`` fallback on an acceptance
collapse and its probes, the draft's frontier after a late adoption,
and ``MXNET_SERVE_SPEC`` gating (the rule, the byte-identity pins and
the int8 plane are tests/test_spec_decode.py's;
docs/architecture/decode_engine.md)."""
import numpy as np
import pytest

from mxnet_tpu.serving import GenerationEngine

from _spec_decode_common import KW, _registry, _run


def test_spec_auto_fallback_on_acceptance_collapse(monkeypatch):
    """MXNET_SERVE_SPEC=auto degrades gracefully: a draft whose
    proposals never survive verification drives the rolling acceptance
    EMA under the floor, after which ticks run plain decode (cheap)
    with occasional speculative probes — token streams stay
    byte-identical throughout.  =force keeps drafting regardless."""
    reqs = [dict(tokens=[7, 3, 11, 29, 4], max_tokens=48, seed=1)]
    base, _ = _run(None, reqs=reqs)
    spec, st = _run("rand", reqs=reqs)
    assert spec == base
    assert st["spec_fallback_steps"] > 0
    assert st["models"]["m"]["spec_acceptance_ema"] < 0.125
    monkeypatch.setenv("MXNET_SERVE_SPEC", "force")
    forced, fst = _run("rand", reqs=reqs)
    assert forced == base
    assert fst["spec_fallback_steps"] == 0
    assert fst["spec_steps"] > st["spec_steps"]


def test_spec_probe_rebuilds_lazily_mirrored_draft(monkeypatch):
    """While fallback is active the draft prefill mirror is skipped
    (zero draft cost per tick); a request admitted entirely inside the
    fallback regime gets its draft KV rebuilt from the PROMPT by the
    probe's chunked catch-up — and the stream stays byte-identical."""
    from mxnet_tpu.serving import decode_engine as de
    monkeypatch.setattr(de, "_SPEC_PROBE_EVERY", 4)
    eng = GenerationEngine(_registry("rand"))
    try:
        eng.submit("m", [7, 3, 11, 29, 4], max_tokens=24).result(180)
        st = eng.stats()
        assert st["models"]["m"]["spec_acceptance_ema"] < 0.125
        toks = eng.submit("m", [2, 5], max_tokens=20).result(180).tokens
        st2 = eng.stats()
    finally:
        eng.close()
    base, _ = _run(None, reqs=[dict(tokens=[2, 5], max_tokens=20,
                                    seed=0)])
    assert toks == base[0]
    assert st2["spec_steps"] > st["spec_steps"]   # probes fired
    assert st2["spec_fallback_steps"] > st["spec_fallback_steps"]


@pytest.mark.parametrize("mirror", [True, False],
                         ids=["mirror-on", "mirror-off"])
def test_spec_draft_frontier_follows_a_late_adoption(mirror, monkeypatch):
    """Four requests over one new prefix, submitted at once: the
    followers adopt its blocks in the tick, after admission.  With the
    prefill mirror on, the adopted blocks hold the draft's rows too
    (the writer's chunks were mirrored) and the draft's frontier moves
    with the target's; with the mirror off (the fallback regime) the
    draft claims nothing of them, and a probe's catch-up rebuilds from
    the prompt.  Either way the streams are the undrafted engine's."""
    from mxnet_tpu.serving import decode_engine as de
    from _paged_common import _submit_at_once
    monkeypatch.setattr(de, "_SPEC_PROBE_EVERY", 4)
    rs = np.random.RandomState(5)
    prefix = [int(t) for t in rs.randint(0, 50, 24)]    # 3 whole blocks
    reqs = [dict(tokens=prefix + [i, 9 - i], max_tokens=10, seed=i)
            for i in range(4)]
    base, _ = _run(None, reqs=reqs)
    eng = GenerationEngine(_registry("self" if mirror else "rand"))
    adopt, seen = eng._adopt_late, []

    def spy(st, i):
        was, held = int(st.dlen[i]), int(st.reg_n[i]) * KW["kv_block"]
        got = adopt(st, i)
        if got[0]:
            seen.append((st.spec_mirror(), min(was, held),
                         int(st.dlen[i]), int(st.prog[i])))
        return got

    eng._adopt_late = spy
    try:
        if not mirror:
            # a draft whose proposals never survive: the EMA collapses
            # and the mirror goes off before the burst arrives
            eng.submit("m", [7, 3, 11, 29, 4], max_tokens=24).result(180)
            assert eng.stats()["models"]["m"]["spec_acceptance_ema"] \
                < 0.125
        toks = [f.result(180).tokens for f in _submit_at_once(eng, reqs)]
        st = eng.stats()
    finally:
        eng.close()
    assert toks == base
    assert st["prefix_late_tokens"] > 0 and seen
    for on, kept, dlen, prog in seen:
        assert on == mirror
        assert dlen == (prog if mirror else kept)
    assert st["spec_steps"] > 0


def test_spec_env_gating(monkeypatch):
    """MXNET_SERVE_SPEC=0 disables speculative decoding even with a
    draft attached — the engine runs plain paged decode, streams
    unchanged."""
    monkeypatch.setenv("MXNET_SERVE_SPEC", "0")
    reqs = [dict(tokens=[7, 3, 11, 29, 4], max_tokens=8, seed=1)]
    spec, st = _run("self", reqs=reqs)
    base, _ = _run(None, reqs=reqs)
    assert spec == base
    assert st["spec_steps"] == 0 and st["spec_draft_steps"] == 0


# ---------------------------------------------------------------------------
# int8 paged KV riding the same pool update
# ---------------------------------------------------------------------------

