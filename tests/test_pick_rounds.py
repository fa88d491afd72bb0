"""Gather-free pick rounds: the Appendix-A start rounds read per-worker
tables back to their candidates (``_bucket_read``), pick per worker
(``_pick_per_bucket``) and move each worker's pick into the slot pool
(``_acquire_slots``) through dense masked reduces over the rounds'
``[C, W]`` one-hot, never a dynamic-index gather.  Each must equal the
gather form it replaced, kept here as the reference; and the lowered
loop's ``sim.ready`` phase must hold no gather that the unrolled rounds
(``max_cores``, ``flow_rounds``) multiply.  The greedy placer loops over
the tasks it places only, and must equal its loop over every task."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.imodes import encode_imode
from repro.core.vectorized import (SIM_PHASES, as_bucketed, encode_graph,
                                   make_bucket_dynamic_simulator,
                                   make_bucket_greedy_placer, pad_spec)
from repro.core.vectorized.sim import (DOWNLOAD_SLOTS, NEG, _acquire_slots,
                                       _bucket_max, _bucket_read, _onehot,
                                       _pick_per_bucket)

import test_vectorized_dynamic as tvd


# ------------------------------------------------ the gather references

def ref_pick_per_bucket(bucket, n_buckets, eligible, *keys):
    onehot = _onehot(bucket, n_buckets)
    cand = eligible
    for k in keys:
        kk = jnp.where(cand, k, NEG)
        mb = _bucket_max(onehot, kk)[bucket]
        cand = cand & (kk == mb) & (mb > NEG)
    idx = jnp.arange(bucket.shape[0], dtype=jnp.float32)
    ii = jnp.where(cand, -idx, NEG)
    mb = _bucket_max(onehot, ii)[bucket]
    return cand & (ii == mb)


def ref_acquire_slots(st, pick, dst_e, src_e, bytes_e, W, ids=None):
    E = pick.shape[0]
    e_ids = jnp.arange(E, dtype=jnp.int32)
    if ids is None:
        ids = e_ids
    onehot = _onehot(dst_e, W)
    pe = jnp.max(jnp.where(onehot & pick[:, None], e_ids[:, None], -1),
                 initial=-1, axis=0)
    occ_w = (st["slot_edge"] >= 0).reshape(W, DOWNLOAD_SLOTS)
    first_free = jnp.argmin(occ_w.astype(jnp.int32), axis=1)
    has_free = ~jnp.all(occ_w, axis=1)
    take = (pe >= 0) & has_free
    pe_c = jnp.clip(pe, 0)
    put = ((jnp.arange(DOWNLOAD_SLOTS)[None, :] == first_free[:, None])
           & take[:, None]).reshape(-1)

    def spread(v):
        return jnp.broadcast_to(v[:, None], (W, DOWNLOAD_SLOTS)).reshape(-1)
    return dict(
        st,
        slot_edge=jnp.where(put, spread(ids[pe_c]), st["slot_edge"]),
        slot_src=jnp.where(put, spread(src_e[pe_c]), st["slot_src"]),
        slot_rem=jnp.where(put, spread(bytes_e[pe_c]), st["slot_rem"]),
        overflow=st["overflow"] | jnp.any((pe >= 0) & ~has_free),
    )


# --------------------------------------------------------- the cases

# (name, W, C): random keys drawn from a few values, so exact ties are
# common; every key NEG; an empty frontier; every candidate in one
# bucket
CASES = [("ties", 1, 24), ("ties", 32, 160), ("all_neg", 1, 12),
         ("all_neg", 32, 64), ("empty", 1, 0), ("empty", 32, 0),
         ("one_bucket", 1, 40), ("one_bucket", 32, 40)]


def make_case(name, W, C, seed=0):
    rng = np.random.default_rng(seed + 1000 * W + C)
    if name == "one_bucket":
        bucket = np.full(C, W - 1, np.int32)
    else:
        bucket = rng.integers(0, W, C).astype(np.int32)
    eligible = rng.random(C) < 0.7
    if name == "all_neg":
        k1 = np.full(C, NEG, np.float32)
        k2 = np.full(C, NEG, np.float32)
    else:
        k1 = rng.integers(0, 3, C).astype(np.float32)
        k2 = -rng.permutation(C).astype(np.float32)   # -id: distinct
    return (jnp.asarray(bucket), jnp.asarray(eligible), jnp.asarray(k1),
            jnp.asarray(k2))


@pytest.mark.parametrize("n_keys", [1, 2])
@pytest.mark.parametrize("name,W,C", CASES)
def test_pick_per_bucket_equals_the_gather_form(name, W, C, n_keys):
    bucket, eligible, k1, k2 = make_case(name, W, C)
    keys = (k1, k2)[:n_keys]
    got = _pick_per_bucket(_onehot(bucket, W), eligible, *keys)
    want = ref_pick_per_bucket(bucket, W, eligible, *keys)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # at most one pick per bucket, and only among eligible rows
    picks = np.bincount(np.asarray(bucket)[np.asarray(got)], minlength=W)
    assert picks.max(initial=0) <= 1
    assert not np.any(np.asarray(got) & ~np.asarray(eligible))


@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("name,W,C", CASES)
def test_bucket_read_equals_the_gather(name, W, C, dtype):
    bucket, eligible, k1, _ = make_case(name, W, C)
    onehot = _onehot(bucket, W)
    rng = np.random.default_rng(W + C)
    if dtype == "int32":
        tables = [rng.integers(-5, 17, W).astype(np.int32),
                  np.full(W, np.iinfo(np.int32).min, np.int32)]
    else:
        tables = [rng.integers(0, 3, W).astype(np.float32),
                  np.full(W, NEG, np.float32),
                  np.where(rng.random(W) < 0.5, -np.inf, -0.0
                           ).astype(np.float32),
                  np.asarray(_bucket_max(onehot, jnp.where(eligible, k1,
                                                           NEG)))]
    for table in tables:
        table = jnp.asarray(table)
        got = _bucket_read(onehot, table)
        assert got.dtype == table.dtype and got.shape == (C,)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(table[bucket]))


def slot_state(W, rng, full_share):
    """A slot pool where each worker's slots are busy with probability
    ``full_share`` each — some workers full, to reach ``overflow``."""
    S = W * DOWNLOAD_SLOTS
    busy = rng.random(S) < full_share
    return dict(slot_edge=jnp.asarray(np.where(busy, rng.integers(0, 99, S),
                                               -1).astype(np.int32)),
                slot_src=jnp.asarray(rng.integers(0, W, S).astype(np.int32)),
                slot_rem=jnp.asarray(rng.random(S).astype(np.float32)),
                overflow=jnp.bool_(False))


@pytest.mark.parametrize("with_ids", [False, True])
@pytest.mark.parametrize("full_share", [0.3, 0.9])
@pytest.mark.parametrize("name,W,C", CASES)
def test_acquire_slots_equals_the_gather_form(name, W, C, full_share,
                                              with_ids):
    bucket, eligible, k1, k2 = make_case(name, W, C)
    rng = np.random.default_rng(7 + W + C)
    st = slot_state(W, rng, full_share)
    pick = ref_pick_per_bucket(bucket, W, eligible, k1, k2)
    src = jnp.asarray(rng.integers(0, W, C).astype(np.int32))
    nbytes = jnp.asarray((rng.random(C) * 1e6).astype(np.float32))
    ids = (jnp.asarray(rng.integers(0, 4096, C).astype(np.int32))
           if with_ids else None)
    got = _acquire_slots(st, pick, _onehot(bucket, W), src, nbytes, ids=ids)
    if C == 0:
        # the gather form cannot index an empty axis at all; with no
        # candidate the pool stays as it was
        want = st
    else:
        want = ref_acquire_slots(st, pick, bucket, src, nbytes, W, ids=ids)
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


# --------------------------------------------------- structural guard

_LOC = re.compile(r'^#(loc\d+) = (.*)$', re.M)
_FUNC = re.compile(r'\s*func\.func (?:private |public )?@([\w.$-]+)')
_CALL = re.compile(r'call @([\w.$-]+)')
_OP_LOC = re.compile(r'loc\(#(loc\d+)\)\s*$')


def gathers_under(text, scope):
    """The ``gather`` ops of a lowered module (``as_text(debug_info=
    True)``) whose location lies under ``scope``, counting those inside
    the private functions that such an op calls."""
    locs = dict(_LOC.findall(text))
    funcs, cur = {}, None
    for line in text.splitlines():
        m = _FUNC.match(line)
        if m:
            cur = m.group(1)
            funcs[cur] = []
        if cur is not None:
            funcs[cur].append(line)

    def total(fn, seen=()):
        n = 0
        for line in funcs.get(fn, []):
            n += '"stablehlo.gather"' in line
            m = _CALL.search(line)
            if m and m.group(1) not in seen:
                n += total(m.group(1), seen + (fn,))
        return n

    n = 0
    for lines in funcs.values():
        for line in lines:
            m = _OP_LOC.search(line)
            if not (m and scope in locs.get(m.group(1), "")):
                continue
            n += '"stablehlo.gather"' in line
            call = _CALL.search(line)
            if call:
                n += total(call.group(1))
    return n


@pytest.mark.parametrize("scheduler,netmodel", [("blevel", "maxmin"),
                                                ("greedy", "simple")])
def test_pick_rounds_add_no_gather_to_sim_ready(scheduler, netmodel):
    g = tvd.mini_fork()
    d, s = encode_imode(g, "user")
    bspec = as_bucketed(encode_graph(g))
    scope = f"while/body/{SIM_PHASES[1]}/"
    counts = {}
    for max_cores, flow_rounds in [(2, 1), (8, 1), (2, 4)]:
        run = make_bucket_dynamic_simulator(
            4, 2, scheduler=scheduler, netmodel=netmodel,
            max_cores=max_cores, flow_rounds=flow_rounds)
        text = jax.jit(run).lower(bspec, d, s, np.float32(0.1),
                                  np.float32(0.05)).as_text(debug_info=True)
        counts[max_cores, flow_rounds] = gathers_under(text, scope)
    # the detection pass and the candidate loads still gather, once an
    # iteration; the rounds add none
    assert counts[2, 1] > 0
    assert counts[2, 1] == counts[8, 1] == counts[2, 4], counts


# ------------------------------------------------------ greedy placer

def ref_greedy_place(cpus, ready_unassigned, cost_tw, load0, cores):
    """The placer as one trip per task of the bucket, placing the ready
    ones in id order."""
    BIG = jnp.int32(np.iinfo(np.int32).max)

    def body(t, st):
        pw, load = st
        active = ready_unassigned[t]
        c = jnp.where(cores >= cpus[t], cost_tw[t], jnp.inf)
        cand = c == jnp.min(c)
        ld = jnp.where(cand, load, BIG)
        cand = cand & (ld == jnp.min(ld))
        w = jnp.argmax(cand).astype(jnp.int32)
        pw = pw.at[t].set(jnp.where(active, w, pw[t]))
        load = load.at[w].add(jnp.where(active, 1, 0))
        return pw, load

    T = cpus.shape[0]
    pw, _ = jax.lax.fori_loop(0, T, body,
                              (jnp.full(T, -1, jnp.int32), load0))
    return pw


@pytest.mark.parametrize("ready_share", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("W", [1, 4])
def test_greedy_placer_equals_its_loop_over_every_task(W, ready_share):
    g = tvd.mini_cpus()
    spec = encode_graph(g)
    bspec = as_bucketed(pad_spec(spec, (32, 32, 32)))
    rng = np.random.default_rng(int(10 * ready_share) + W)
    T = bspec.T
    ready = jnp.asarray((rng.random(T) < ready_share)
                        & np.asarray(bspec.task_valid))
    # few distinct costs and loads, so both tie-breaks decide
    cost_tw = jnp.asarray(rng.integers(0, 3, (T, W)).astype(np.float32))
    load0 = jnp.asarray(rng.integers(0, 2, W).astype(np.int32))
    cores = jnp.asarray(rng.integers(1, 4, W).astype(np.int32))
    place = jax.jit(make_bucket_greedy_placer(W, None))
    got = place(bspec, ready, cost_tw, load0, cores)
    want = ref_greedy_place(jnp.asarray(bspec.cpus), ready, cost_tw, load0,
                            cores)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and batched, where the lanes need different numbers of trips
    lanes = jnp.stack([ready, jnp.zeros_like(ready), ready.at[:5].set(False)])
    got_b = jax.vmap(place, in_axes=(None, 0, None, None, None))(
        bspec, lanes, cost_tw, load0, cores)
    for lane, row in zip(lanes, got_b, strict=True):
        np.testing.assert_array_equal(
            np.asarray(row),
            np.asarray(ref_greedy_place(jnp.asarray(bspec.cpus), lane,
                                        cost_tw, load0, cores)))
