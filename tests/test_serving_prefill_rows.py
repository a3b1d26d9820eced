"""Row compaction of the fused prefill dispatch over a paged cache.

A paged engine runs each prefill dispatch on ``prefill_rows`` rows (R),
fewer than ``max_batch`` (B) where ``prefill_chunk`` is large: the
program gathers the rows that prefill and scatters their outputs back to
the slot-indexed arrays.  Every case here reaches R < B and serves the
same requests as a dense-cache engine (R = B) and as an engine that
serves one request at a time; the tokens must match exactly."""

import dataclasses
import random

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.storage import ObjectStore
from repro.models import Model
from repro.serving.engine import PREFILL_SLOTS, Request, ServeEngine, prefill_rows
from repro.serving.prefix_store import PrefixStore

MAX_LEN, PAGE = 512, 16
# (max_batch, prefill_chunk): R = 4 and R = 2
WIDE, LONG = (8, 128), (4, 256)


def _model(cfg):
    model = Model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def gqa():
    return _model(reduced(get_arch("ds-paper-100m")))


@pytest.fixture(scope="module")
def mla():
    """A dense arch with MLA attention: fused prefill over latent pages
    (deepseek-v2 itself is MoE, which has no fused prefill)."""
    ds = reduced(get_arch("deepseek-v2-236b"))
    keys = ("use_mla", "kv_lora_rank", "q_lora_rank", "rope_head_dim",
            "nope_head_dim", "v_head_dim", "head_dim")
    cfg = dataclasses.replace(reduced(get_arch("ds-paper-100m")),
                              **{k: getattr(ds, k) for k in keys})
    return _model(cfg)


def prompts(n, seed, lo=20, hi=300):
    rng = random.Random(seed)
    return [[rng.randrange(1, 256) for _ in range(rng.randint(lo, hi))] for _ in range(n)]


def requests(ps, max_new=5, temperature=0.0):
    return [Request(uid=f"r{i}", prompt=list(p), max_new_tokens=max_new,
                    temperature=temperature) for i, p in enumerate(ps)]


def outputs(eng):
    return {r.uid: r.output for r in eng.finished}


def serve(model, params, reqs, geometry, **kw):
    B, C = geometry
    eng = ServeEngine(model, params, max_batch=B, max_len=MAX_LEN, prefill_chunk=C,
                      rng_seed=3, **kw)
    eng.submit(reqs)
    eng.run_to_completion()
    return eng


def paged(model, params, reqs, geometry, **kw):
    eng = serve(model, params, reqs, geometry, cache_mode="paged", page_size=PAGE, **kw)
    B, C = geometry
    assert eng.prefill_rows == prefill_rows(C, B) < B
    assert eng.prefill_slots == eng.prefill_dispatches * eng.prefill_rows * C
    return eng


def ingested(eng):
    """Prompt tokens dispatched plus those a prefix hit let it skip."""
    return eng.prompt_tokens_ingested + eng.prompt_tokens_skipped


def references(model, params, make, geometry, **kw):
    """Tokens of the dense-cache engine (R = B) and of one request at a time."""
    dense = serve(model, params, make(), geometry, **kw)
    assert dense.prefill_rows == geometry[0]
    solo = serve(model, params, make(), (1, geometry[1]), **kw)
    assert outputs(dense) == outputs(solo)
    return outputs(dense)


def test_rows_per_dispatch():
    assert PREFILL_SLOTS == 512
    assert prefill_rows(64, 64) == 8  # granite-34b-l4's cell
    assert prefill_rows(128, 8) == 4 and prefill_rows(256, 4) == 2
    assert prefill_rows(100, 64) == 4  # 5 rows fit; the power of two below
    assert prefill_rows(16, 4) == 4  # clamped to max_batch
    assert prefill_rows(1024, 8) == 1


@pytest.mark.parametrize("geometry", [WIDE, LONG], ids=["R4", "R2"])
@pytest.mark.parametrize("case", ["one_row", "R_rows", "R_plus_1_rows"])
def test_rows_match_dense_and_one_at_a_time(gqa, geometry, case):
    model, params = gqa
    B, C = geometry
    R = prefill_rows(C, B)
    n = {"one_row": 1, "R_rows": R, "R_plus_1_rows": R + 1}[case]
    ps = prompts(n, seed=n + C, lo=10, hi=C)  # one chunk each

    def make():
        return requests(ps)

    want = references(model, params, make, geometry)
    eng = ServeEngine(model, params, max_batch=B, max_len=MAX_LEN, prefill_chunk=C,
                      rng_seed=3, cache_mode="paged", page_size=PAGE)
    eng.submit(make())
    eng.step()
    # every prompt ingested in the first tick: R + 1 rows take two dispatches
    assert eng.prefill_dispatches == -(-n // R)
    assert ingested(eng) == sum(map(len, ps))
    eng.run_to_completion()
    assert outputs(eng) == want


def test_multi_chunk_prompts_and_temperature(gqa):
    model, params = gqa
    ps = prompts(7, seed=11, lo=100, hi=400)

    def make():
        return requests(ps, max_new=6, temperature=0.7)

    want = references(model, params, make, WIDE)
    eng = paged(model, params, make(), WIDE)
    assert outputs(eng) == want
    assert ingested(eng) == sum(map(len, ps))


@pytest.mark.parametrize("geometry", [WIDE, LONG], ids=["R4", "R2"])
def test_budget_with_rotation(gqa, geometry):
    model, params = gqa
    ps = prompts(6, seed=5, lo=150, hi=300)

    def make():
        return requests(ps)

    want = references(model, params, make, geometry, prefill_token_budget=100)
    eng = paged(model, params, make(), geometry, prefill_token_budget=100)
    assert outputs(eng) == want
    assert ingested(eng) == sum(map(len, ps))


def test_preemption_in_the_reservation_pass(gqa):
    model, params = gqa
    ps = prompts(6, seed=9, lo=60, hi=120)

    def make():
        return requests(ps)

    want = references(model, params, make, WIDE)
    eng = paged(model, params, make(), WIDE, total_pages=16)
    assert eng.preemptions > 0
    assert outputs(eng) == want


def test_prefix_hits_with_copy_on_write(gqa):
    model, params = gqa
    base = prompts(1, seed=21, lo=150, hi=150)[0]
    tails = prompts(5, seed=22, lo=3, hi=40)

    def make():
        # shared prefixes end inside a page: partial-page stitches copy it
        return [Request(uid="a", prompt=base + [1, 2], max_new_tokens=4)] + [
            Request(uid=f"b{i}", prompt=base[:150 - 7 * i] + t, max_new_tokens=4)
            for i, t in enumerate(tails)]

    def drive(eng):
        reqs = make()
        eng.submit(reqs[:1])
        while eng.scheduler.slots[0].req is None or eng.scheduler.slots[0].remaining_prompt:
            eng.step()
        eng.submit(reqs[1:])
        eng.run_to_completion()
        return outputs(eng)

    B, C = WIDE
    kw = dict(max_len=MAX_LEN, prefill_chunk=C, rng_seed=3)
    want = drive(ServeEngine(model, params, max_batch=B, **kw))
    assert want == drive(ServeEngine(model, params, max_batch=1, **kw))
    eng = ServeEngine(model, params, max_batch=B, cache_mode="paged", page_size=PAGE, **kw)
    assert drive(eng) == want
    assert eng.prefill_rows == 4
    assert eng.prefix_hit_tokens > 0 and eng.cow_partial_stitches > 0


def test_paged_mla(mla):
    model, params = mla
    ps = prompts(6, seed=31, lo=20, hi=300)

    def make():
        return requests(ps)

    want = references(model, params, make, WIDE)
    eng = paged(model, params, make(), WIDE)
    assert "kv_pages" in eng.cache
    assert outputs(eng) == want


def test_host_sampling(gqa):
    model, params = gqa
    ps = prompts(6, seed=41, lo=20, hi=300)

    def make():
        return requests(ps, temperature=0.7)

    want = references(model, params, make, WIDE, sample_on_device=False)
    eng = paged(model, params, make(), WIDE, sample_on_device=False)
    assert outputs(eng) == want


def test_prefill_worker_hands_off(gqa, tmp_path):
    """A prefill-role engine ingests with compacted dispatches; a decode
    engine takes its handoffs and emits the dense engine's tokens."""
    model, params = gqa
    ps = prompts(5, seed=51, lo=20, hi=300)

    def make():
        return requests(ps)

    want = references(model, params, make, LONG)
    store = ObjectStore(str(tmp_path / "store"))
    pre = paged(model, params, make(), LONG, prefix_store=PrefixStore(store, "ns"),
                worker_role="prefill")
    assert pre.tokens_emitted == 0 and pre.decode_dispatches == 0
    assert ingested(pre) == sum(map(len, ps))
    B, C = LONG
    dec = ServeEngine(model, params, max_batch=B, max_len=MAX_LEN, prefill_chunk=C,
                      rng_seed=3, cache_mode="paged", page_size=PAGE,
                      prefix_store=PrefixStore(store, "ns"), worker_role="decode")
    for r in pre.finished:
        dec.submit_handoff({"uid": r.uid, "prompt": list(r.prompt), "output": [],
                            "sample_stream": r.sample_stream,
                            "max_new_tokens": r.max_new_tokens,
                            "temperature": r.temperature, "stop_token": r.stop_token})
    dec.run_to_completion()
    assert outputs(dec) == want
    assert dec.handoff_fallbacks == 0


@pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
def test_model_runs_R_by_C(gqa, on_device):
    """The prefill program's model inputs are (R, C): its hidden states
    are (R, C, d) and never (B, C, d); its outputs stay (B, ...)."""
    model, params = gqa
    B, C = WIDE
    eng = ServeEngine(model, params, max_batch=B, max_len=MAX_LEN, prefill_chunk=C,
                      cache_mode="paged", page_size=PAGE, total_pages=64,
                      sample_on_device=on_device)
    R, d = eng.prefill_rows, model.cfg.d_model
    vec = np.zeros((B,), np.int32)
    args = [params, eng.cache, np.zeros((B, C), np.int32), vec, vec]
    if on_device:
        args += [np.zeros((B,), np.float32), vec, vec, vec, vec]
    text = str(jax.make_jaxpr(eng._prefill)(*args))
    assert f"f32[{R},{C},{d}]" in text
    assert f"[{B},{C},{d}]" not in text
    outs = jax.eval_shape(eng._prefill, *args)
    assert outs[0].shape[0] == B
    assert outs[-1]["page_table"].shape == eng.cache["page_table"].shape


def test_dense_and_ssm_engines_run_every_row(gqa):
    model, params = gqa
    B, C = WIDE
    dense = ServeEngine(model, params, max_batch=B, max_len=MAX_LEN, prefill_chunk=C)
    assert dense.prefill_rows == B
    ssm_model, ssm_params = _model(reduced(get_arch("mamba2-1.3b")))
    ssm = ServeEngine(ssm_model, ssm_params, max_batch=B, max_len=MAX_LEN, prefill_chunk=C)
    assert ssm.prefill_rows == B
