"""The ladder of prefill row widths (ISSUE 32): a whole-row prefill is
launched at the narrowest of a few fixed widths that holds the
request's own context, so a short prompt does not multiply a row of
padding as wide as the longest prompt allowed.

What is pinned here: how the ladder follows from ``prefill_budget`` and
the attention forward's routes; that the rung is a function of the
context length alone; that a row of any rung gives the full-width row's
first token, logits and K/V; that preemption, which re-prefills at
another rung, stays invisible.  The batched == sequential contract over
all rungs is ``test_serving.py``'s, the no-compile-after-warm-up pin
``test_analysis.py``'s, the ``engine.prefill`` span's ``S``
``test_step_phases.py``'s, the window pool's ``test_serving_afmoe.py``'s.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.serving import (ServingEngine, ServingModelConfig, SimClock,
                              init_params)
from apex_tpu.serving.engine import (MIN_PREFILL_ROW, prefill_ladder,
                                     prefill_route)

pytestmark = pytest.mark.serving

# two rungs, as the 1.3B cells have on the chip: the row and its half
CFG = ServingModelConfig(vocab_size=64, hidden_size=32, num_heads=4,
                         num_layers=2, max_position=256)
RUNGS = (128, 256)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


def _engine(params, **kw):
    kw.setdefault("num_pages", 160)
    return ServingEngine(CFG, params, page_size=8, max_batch=4,
                         clock=SimClock(), **kw)


def _prompt(n, seed=0):
    return [int(x) for x in
            np.random.RandomState(seed + n).randint(0, CFG.vocab_size, n)]


def _spy_on_rows(eng):
    """The widths of the rows ``eng`` launches from here on."""
    inner, widths = eng._prefill_fn, []

    def spy(p, tokens, *rest):
        widths.append(int(tokens.shape[1]))
        return inner(p, tokens, *rest)

    eng._prefill_fn = spy
    return widths


def _kernel_takes(width):
    """The TPU forward's rule at ``flash_attention``'s blocks of 512
    queries by 1,024 keys, as a route."""
    ok = width % min(512, width) == 0 and width % min(1024, width) == 0
    return "varlen" if ok else "xla"


@pytest.mark.parametrize("budget,route,want", [
    (2048, _kernel_takes, (1024, 2048)),
    (1024, _kernel_takes, (512, 1024)),
    (3072, _kernel_takes, (3072,)),               # 1,536 keys: no block
    (1536, _kernel_takes, (768, 1536)),           # both off the kernel
    (2048, lambda w: "xla", (1024, 2048)),
    (1001, lambda w: "xla", (501, 1001)),
    (256, lambda w: "xla", (128, 256)),
    (255, lambda w: "xla", (128, 255)),
    (254, lambda w: "xla", (254,)),               # 127 is under the floor
    (96, lambda w: "xla", (96,)),
    (1, lambda w: "xla", (1,)),
], ids=lambda v: str(v) if isinstance(v, int) else None)
def test_the_ladder_is_the_row_and_its_half_where_the_forward_takes_both_alike(
        budget, route, want):
    ladder = prefill_ladder(budget, route)
    assert ladder == want
    assert ladder[-1] == budget and list(ladder) == sorted(set(ladder))
    assert all(w >= MIN_PREFILL_ROW for w in ladder[:-1])


@pytest.mark.parametrize("tp", [1, 4])
def test_on_the_tpu_the_flagship_row_keeps_the_rungs_on_the_varlen_route(
        monkeypatch, tp):
    cfg = ServingModelConfig(51200, 2048, 16, 24, max_position=2048,
                             dtype=jnp.bfloat16)
    route = functools.partial(prefill_route, cfg, tp)
    assert route(2048) == "xla"                   # here, on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert {w: route(w) for w in (512, 1024, 1536, 2048)} == {
        512: "varlen", 1024: "varlen", 1536: "xla", 2048: "varlen"}
    assert prefill_ladder(2048, route) == (1024, 2048)
    assert prefill_ladder(3072, route) == (3072,)


def test_the_rung_is_the_narrowest_that_holds_the_context(params):
    eng = _engine(params)
    assert eng.prefill_widths == RUNGS
    assert eng.prefill_widths[-1] == eng.prefill_budget
    for c in range(1, eng.prefill_budget + 1):
        width = eng.prefill_width(c)
        assert width in RUNGS and width >= c
        assert all(w < c for w in RUNGS if w < width)
    with pytest.raises(IndexError):
        eng.prefill_width(eng.prefill_budget + 1)


def test_the_rung_follows_the_context_and_not_the_company(params):
    # the same prompt, alone and behind three others of other rungs:
    # the row launched for it is the same
    def widths_of(prompts):
        eng = _engine(params)
        widths = _spy_on_rows(eng)
        for p in prompts:
            eng.submit(p, 2)
        eng.run()
        return widths

    crowd = [_prompt(n) for n in (200, 30, 128, 129)]
    together = widths_of(crowd)
    assert together == [256, 128, 128, 256]
    for p, width in zip(crowd, together):
        assert widths_of([p]) == [width]


# fp32 on the CPU: a row of another width sums the same products in
# another blocking, so logits and K/V agree to rounding, not bit for bit
LOGIT_TOL = 2e-5


@pytest.mark.parametrize("context", [5, 127, 128, 129, 200, 253])
def test_a_rung_gives_the_full_width_rows_first_token_logits_and_kv(
        params, context):
    eng = _engine(params)
    prompt = _prompt(context)

    def row(width):
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :context] = prompt
        seg = np.zeros((1, width), np.int32)
        seg[0, :context] = 1
        pos = np.zeros((1, width), np.int32)
        pos[0, :context] = np.arange(context)
        logits, k, v = eng.decoder.prefill(
            params, jnp.asarray(tokens), jnp.asarray(seg), jnp.asarray(pos),
            np.int32(context - 1))
        return (np.asarray(logits[0, 0]), np.asarray(k[:, 0, :context]),
                np.asarray(v[:, 0, :context]))

    rung = eng.prefill_width(context)
    logits, k, v = row(rung)
    wide_logits, wide_k, wide_v = row(eng.prefill_budget)
    assert np.max(np.abs(logits - wide_logits)) <= LOGIT_TOL
    assert np.max(np.abs(k - wide_k)) <= LOGIT_TOL
    assert np.max(np.abs(v - wide_v)) <= LOGIT_TOL
    # what the engine serves first is what the widest row puts first,
    # and the K/V it scattered is the K/V of that row
    req = eng.submit(prompt, 3)
    eng.step()
    assert req.generated[0] == int(np.argmax(wide_logits))
    pages = np.asarray(req.pages)[np.arange(context) // 8]
    got = np.asarray(eng.cache.k)[:, pages, np.arange(context) % 8]
    assert np.max(np.abs(got - wide_k)) <= LOGIT_TOL


def test_reprefill_after_preemption_takes_the_rung_of_the_longer_context(
        params):
    # prompts that end just under the half, so that the tokens generated
    # before an eviction push the re-prefill onto the whole row
    # (eviction takes the newest)
    prompts = [_prompt(n) for n in (60, 120, 185, 125)]
    roomy = _engine(params)
    want = [roomy.submit(p, 24) for p in prompts]
    roomy.run()
    # 69 pages hold the four prompts and not their outputs
    tight = _engine(params, num_pages=70)
    widths = _spy_on_rows(tight)
    got = [tight.submit(p, 24) for p in prompts]
    tight.run()
    assert sum(r.preemptions for r in got) >= 1
    assert [r.generated for r in got] == [r.generated for r in want]
    assert widths[:4] == [128, 128, 256, 128]
    assert widths[4:] and set(widths[4:]) == {256}    # 125 + what it had made
    assert tight.cache.pages_used == 0


def test_recover_rebuilds_the_same_ladder(params):
    eng = _engine(params)
    before = eng.prefill_widths
    req = eng.submit(_prompt(150), 4)
    eng.step()
    eng.recover("test")
    eng.run()
    assert eng.prefill_widths == before
    assert req.finish_reason == "length" and len(req.generated) == 4
