"""DistributedOptimizer (torch) semantics: hook-driven allreduce,
backward_passes_per_step, compression, parameter/optimizer broadcast,
object collectives — single-process plus real 2-process jobs
(reference ``test/parallel/test_torch.py`` tier)."""

import os

import numpy as np
import pytest
import torch
import torch.nn as nn

import horovod_tpu.torch as hvd
from horovod_tpu.runner import run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "tests")]),
}


def _model(seed=0):
    torch.manual_seed(seed)
    return nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))


def test_single_process_wraps_transparently():
    hvd.init()
    model = _model()
    base = torch.optim.SGD(model.parameters(), lr=0.1)
    opt = hvd.DistributedOptimizer(
        base, named_parameters=model.named_parameters())
    assert isinstance(opt, torch.optim.SGD)
    x = torch.randn(8, 4)
    loss = model(x).pow(2).mean()
    opt.zero_grad()
    loss.backward()
    opt.step()  # size==1: plain step, no collectives needed


def test_duplicate_names_rejected():
    hvd.init()
    model = _model()
    with pytest.raises(ValueError, match="unique"):
        hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=[("same", p) for p in model.parameters()])


def test_incomplete_named_parameters_rejected():
    hvd.init()
    model = _model()
    with pytest.raises(ValueError, match="cover"):
        hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=list(model.named_parameters())[:1])


def _two_rank_step(compression_name, backward_passes):
    """Worker: one (or two) backward passes with rank-dependent data;
    returns the parameter vector after step() for cross-rank and
    vs-manual comparison."""
    import numpy as np
    import torch
    import torch.nn as nn
    import horovod_tpu.torch as hvd

    hvd.init()
    r = hvd.rank()
    torch.manual_seed(7)  # identical init on every rank
    model = nn.Linear(3, 1, bias=False)
    compression = {"none": hvd.Compression.none,
                   "fp16": hvd.Compression.fp16}[compression_name]
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.5),
        named_parameters=model.named_parameters(),
        compression=compression,
        backward_passes_per_step=backward_passes)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)

    for pass_idx in range(backward_passes):
        x = torch.full((2, 3), float(r + 1 + pass_idx))
        loss = model(x).sum()
        loss.backward()
    opt.step()
    out = model.weight.detach().numpy().copy().ravel().tolist()
    hvd.shutdown()
    return out


@pytest.mark.parametrize("compression", [
    "none", pytest.param("fp16", marks=pytest.mark.slow)])
def test_two_rank_grad_average(compression):
    results = run(_two_rank_step, args=(compression, 1), np=2,
                  env=_WORKER_ENV, start_timeout=90)
    assert np.allclose(results[0], results[1]), results
    # Manual model: grad of sum(w.x) over batch of 2 rows of value v is
    # 2*v per weight; ranks v=1,2 -> avg grad 3; w_new = w0 - 0.5*3.
    torch.manual_seed(7)
    w0 = nn.Linear(3, 1, bias=False).weight.detach().numpy().ravel()
    expect = w0 - 0.5 * 3.0
    atol = 1e-5 if compression == "none" else 5e-2
    assert np.allclose(results[0], expect, atol=atol), (results[0], expect)


def _adasum_step_worker():
    """DistributedOptimizer(op=Adasum): the applied update must be the
    native core's VHDD combine of the per-rank gradients."""
    import numpy as np
    import torch
    import torch.nn as nn
    import horovod_tpu.torch as hvd

    hvd.init()
    r = hvd.rank()
    torch.manual_seed(7)
    model = nn.Linear(3, 1, bias=False)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1.0),
        named_parameters=model.named_parameters(), op=hvd.Adasum)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    x = torch.tensor([[float(r + 1), 0.0, 0.0],
                      [0.0, float(2 - r), 0.0]])
    model(x).sum().backward()
    opt.step()
    out = model.weight.detach().numpy().copy().ravel().tolist()
    hvd.shutdown()
    return out


@pytest.mark.slow  # redundancy: adasum math + the host data plane are
# pinned by tests/test_adasum.py's fast-tier np=2 cases, and the
# DistributedOptimizer op= plumbing this adds is the same wrapper path
# test_two_rank_grad_average drives every run — slow tier keeps the
# full composition without paying a ~22s spawn in tier-1.
def test_two_rank_adasum_optimizer():
    from _adasum_model import adasum_fold_model

    results = run(_adasum_step_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    assert np.allclose(results[0], results[1]), results
    torch.manual_seed(7)
    w0 = nn.Linear(3, 1, bias=False).weight.detach().numpy().ravel()
    # grad of sum(w.x): rank 0 -> [1, 2, 0], rank 1 -> [2, 1, 0]
    g = adasum_fold_model([np.array([1.0, 2.0, 0.0], np.float32),
                           np.array([2.0, 1.0, 0.0], np.float32)])
    expect = w0 - g
    assert np.allclose(results[0], expect, atol=1e-5), (results[0], expect)


@pytest.mark.slow  # ISSUE 10 budget headroom: the accumulate counter
# is single-path python bookkeeping around the SAME _two_rank_step
# worker test_two_rank_grad_average gates in tier-1 — the ~22 s torch
# np=2 spawn re-proves the wire, not the counter.
def test_backward_passes_per_step_accumulates():
    results = run(_two_rank_step, args=("none", 2), np=2,
                  env=_WORKER_ENV, start_timeout=90)
    assert np.allclose(results[0], results[1])
    # Pass 1: ranks contribute v=1,2; pass 2: v=2,3. Local grads
    # accumulate: rank0 2*(1+2)=6, rank1 2*(2+3)=10 -> avg 8.
    torch.manual_seed(7)
    w0 = nn.Linear(3, 1, bias=False).weight.detach().numpy().ravel()
    expect = w0 - 0.5 * 8.0
    assert np.allclose(results[0], expect, atol=1e-5), (results[0], expect)


def _broadcast_state_worker():
    import torch
    import torch.nn as nn
    import horovod_tpu.torch as hvd

    hvd.init()
    r = hvd.rank()
    torch.manual_seed(100 + r)  # DIFFERENT init per rank
    model = nn.Linear(2, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.1 * (r + 1),
                          momentum=0.9)
    # Root is rank 1 — exercises the nonzero-root path.
    hvd.broadcast_parameters(model.state_dict(), root_rank=1)
    hvd.broadcast_optimizer_state(opt, root_rank=1)
    digest = sorted((k, v.sum().item())
                    for k, v in model.state_dict().items())
    lr = opt.param_groups[0]["lr"]
    hvd.shutdown()
    return digest, lr


@pytest.mark.slow  # ~27s spawn; redundancy (ISSUE 11 budget audit):
# the nonzero-root broadcast COLLECTIVE is pinned tier-1 by the eager
# multiprocess scenarios (numpy + jax tiers both broadcast from
# root s-1), and the broadcast_parameters wrapper runs tier-1 inside
# test_two_rank_grad_average's worker and test_jax_optimizer's pytree
# tier — the unique surface here (broadcast_optimizer_state's
# state-dict walk from a nonzero root) is pure-Python glue over those
# pinned paths.
def test_broadcast_parameters_and_optimizer_state_nonzero_root():
    results = run(_broadcast_state_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    assert results[0] == results[1]
    assert results[0][1] == pytest.approx(0.2)  # rank 1's lr everywhere


def _object_worker():
    import horovod_tpu.torch as hvd
    hvd.init()
    r = hvd.rank()
    gathered = hvd.allgather_object({"rank": r, "data": list(range(r + 1))})
    rooted = hvd.broadcast_object(
        {"from": hvd.rank()} if r == 1 else None, root_rank=1)
    hvd.shutdown()
    return gathered, rooted


@pytest.mark.slow  # ISSUE 10 budget headroom: object collectives are
# pickle framing over the allgather/broadcast byte paths the eager
# digests gate per-bit; the framing itself is deterministic rank-local
# python — ~14 s of np=2 torch spawn.
def test_object_collectives():
    results = run(_object_worker, np=2, env=_WORKER_ENV, start_timeout=90)
    for gathered, rooted in results:
        assert gathered == [{"rank": 0, "data": [0]},
                            {"rank": 1, "data": [0, 1]}]
        assert rooted == {"from": 1}


def _zero_grad_guard_worker():
    import torch
    import torch.nn as nn
    import horovod_tpu.torch as hvd

    hvd.init()
    model = nn.Linear(2, 1)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    loss = model(torch.ones(1, 2)).sum()
    loss.backward()
    try:
        opt.zero_grad()
        raised = False
    except AssertionError:
        raised = True
    opt.step()  # drain the pending handles so shutdown is clean
    hvd.shutdown()
    return raised


@pytest.mark.slow  # heavy multiprocess spawn; coverage overlaps the
# fast tier — keeps tier-1 inside its wall-clock budget
def test_zero_grad_between_backward_and_step_raises():
    results = run(_zero_grad_guard_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    assert results == [True, True]


# ---------------------------------------------------------------------------
# sparse gradients (reference torch/optimizer.py:215 sparse->allgather)
# ---------------------------------------------------------------------------

def _sparse_worker(sparse_as_dense):
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    torch.manual_seed(0)
    emb = torch.nn.Embedding(6, 3, sparse=True)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(emb.parameters(), lr=0.0),
        named_parameters=emb.named_parameters(),
        sparse_as_dense=sparse_as_dense)
    idx = torch.tensor([0, 2]) if hvd.rank() == 0 else torch.tensor([2, 5])
    emb(idx).sum().backward()
    opt.synchronize()
    with opt.skip_synchronize():
        opt.step()
    g = emb.weight.grad
    dense = g.to_dense() if g.is_sparse else g
    was_sparse = g.is_sparse
    hvd.shutdown()
    return dense.detach().numpy(), was_sparse


# Both arms slow-tier (ISSUE 10 budget headroom): the arms differ only
# in the sparse_as_dense flag inside one worker body, the sparse→dense
# packaging is rank-local torch glue, and the allreduce it feeds is the
# tier-1-gated two-rank path — ~22 s of np=2 torch spawn per arm.
@pytest.mark.parametrize("sparse_as_dense", [
    pytest.param(False, marks=pytest.mark.slow),
    pytest.param(True, marks=pytest.mark.slow)])
def test_sparse_gradients_average(sparse_as_dense):
    from functools import partial

    results = run(partial(_sparse_worker, sparse_as_dense), np=2,
                  env=_WORKER_ENV, start_timeout=90)
    expected = np.zeros((6, 3), np.float32)
    expected[0], expected[2], expected[5] = 0.5, 1.0, 0.5
    for dense, was_sparse in results:
        assert was_sparse  # reduced grad handed back sparse either way
        np.testing.assert_allclose(dense, expected, rtol=1e-6)


def _sparse_skip_worker():
    """Step 2 skips the embedding on rank 0 only: the missing-grad
    fill-in must launch the *sparse* collective pair, not dense zeros."""
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    torch.manual_seed(0)
    emb = torch.nn.Embedding(4, 2, sparse=True)
    lin = torch.nn.Linear(2, 1)
    params = ([("emb." + k, v) for k, v in emb.named_parameters()]
              + [("lin." + k, v) for k, v in lin.named_parameters()])
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD([p for _, p in params], lr=0.0),
        named_parameters=params)
    # step 1: both ranks touch the embedding (sparse layout learned)
    (emb(torch.tensor([hvd.rank()])).sum() + lin(torch.ones(2))).backward()
    opt.step()
    opt.zero_grad()
    # step 2: rank 0 skips the embedding entirely (grad None)
    if hvd.rank() == 0:
        lin(torch.ones(2)).sum().backward()
    else:
        (emb(torch.tensor([3])).sum() + lin(torch.ones(2))).backward()
    opt.step()
    g = emb.weight.grad.to_dense().detach().numpy()
    hvd.shutdown()
    return g


@pytest.mark.slow  # heavy multiprocess spawn; a sibling variant in
# the fast tier keeps this coverage — tier-1 wall-clock budget
def test_sparse_missing_grad_launches_sparse_collective():
    results = run(_sparse_skip_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    expected = np.zeros((4, 2), np.float32)
    expected[3] = 0.5  # rank 1's row-3 ones, averaged over 2 ranks
    for g in results:
        np.testing.assert_allclose(g, expected, rtol=1e-6)


# ---------------------------------------------------------------------------
# gradient grouping (reference `groups` arg)
# ---------------------------------------------------------------------------

def _groups_worker(groups_spec):
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    torch.manual_seed(1)
    model = torch.nn.Sequential(
        torch.nn.Linear(4, 8), torch.nn.ReLU(), torch.nn.Linear(8, 2))
    if groups_spec == "explicit":
        groups = [[model[0].weight, model[2].weight]]  # biases individual
    else:
        groups = groups_spec
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters(), groups=groups)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    for step in range(3):
        x = torch.randn(4, 4, generator=torch.Generator().manual_seed(
            100 + step * 2 + hvd.rank()))
        opt.zero_grad()
        model(x).pow(2).sum().backward()
        opt.step()
    out = [p.detach().clone().numpy() for p in model.parameters()]
    hvd.shutdown()
    return out


@pytest.fixture(scope="module")
def ungrouped_baseline():
    from functools import partial
    return run(partial(_groups_worker, None), np=2, env=_WORKER_ENV,
               start_timeout=90)


# Both variants slow-tier (ISSUE 10 budget headroom): the int-groups
# call plus the module-scoped ungrouped baseline fixture cost ~44 s of
# tier-1 for a parity the wire already gates — the torch int8
# optimizer digest test drives grouped_allreduce_async through
# _DistributedOptimizer to bit-identical np=2 digests, and the native
# grouped fusion path is digest-pinned by the eager tier
# (transport_digest's grp arm, the fused-bitwise matrix).
@pytest.mark.parametrize("groups_spec", [
    pytest.param(2, marks=pytest.mark.slow),
    pytest.param("explicit", marks=pytest.mark.slow)])
def test_groups_match_ungrouped(groups_spec, ungrouped_baseline):
    from functools import partial

    results = run(partial(_groups_worker, groups_spec), np=2,
                  env=_WORKER_ENV, start_timeout=90)
    # Both ranks identical, and grouping must not change the math:
    # compare against the ungrouped reference run.
    for a, b in zip(results[0], results[1]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(results[0], ungrouped_baseline[0]):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_groups_validated_at_size_one():
    hvd.init()
    model = _model()
    with pytest.raises(ValueError, match="positive int"):
        hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(), groups=-1)
    with pytest.raises(ValueError, match="not a gradient-requiring"):
        hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(),
            groups=[[torch.zeros(3)]])


def _groups_skip_worker():
    """Rank 0 skips the second linear on step 2: its group must be
    force-completed at synchronize() with zero-filled grads, keeping
    both ranks on identical grouped collectives."""
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    torch.manual_seed(1)
    lin1, lin2 = torch.nn.Linear(4, 4), torch.nn.Linear(4, 4)
    params = ([("l1." + k, v) for k, v in lin1.named_parameters()]
              + [("l2." + k, v) for k, v in lin2.named_parameters()])
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD([p for _, p in params], lr=0.1),
        named_parameters=params, groups=2)
    hvd.broadcast_parameters(dict(params), root_rank=0)
    x = torch.ones(2, 4)
    for step in range(3):
        opt.zero_grad()
        y = lin1(x)
        if not (step == 1 and hvd.rank() == 0):
            y = lin2(y)
        y.sum().backward()
        opt.step()
    out = [p.detach().clone().numpy() for _, p in params]
    hvd.shutdown()
    return out


@pytest.mark.slow  # heavy multiprocess spawn; a sibling variant in
# the fast tier keeps this coverage — tier-1 wall-clock budget
def test_groups_force_complete_on_skip():
    results = run(_groups_skip_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    for a, b in zip(results[0], results[1]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# in-place op variants + compression kwarg (reference torch/mpi_ops.py)
# ---------------------------------------------------------------------------

def _inplace_ops_worker():
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    r = hvd.rank()
    t = torch.full((3,), float(r + 1))
    same = hvd.allreduce_(t, op=hvd.Sum, name="ip.ar")
    assert same is t  # result landed in the argument
    ar = t.clone()

    b = torch.full((2,), float(r * 10))
    hvd.broadcast_(b, root_rank=1, name="ip.bc")

    g1, g2 = torch.full((2,), float(r)), torch.full((2,), float(r + 5))
    outs = hvd.grouped_allreduce_([g1, g2], op=hvd.Sum, name="ip.gar")
    assert outs[0] is g1 and outs[1] is g2

    # compression kwarg on the convenience form
    c = hvd.allreduce(torch.full((4,), float(r + 1)), op=hvd.Sum,
                      compression=hvd.Compression.fp16, name="ip.comp")

    out = (ar.numpy().tolist(), b.numpy().tolist(),
           g1.numpy().tolist(), g2.numpy().tolist(), c.numpy().tolist())
    hvd.shutdown()
    return out


def test_inplace_ops_single_process():
    """The in-place API glue at size 1: results land IN the argument
    tensor (aliasing contract), grouped returns the same objects, and
    the compression kwarg is accepted — everything the wrapper layer
    adds over the native submit path, without a spawn. The cross-rank
    averaging of that same native plane is pinned in tier-1 by
    test_two_rank_grad_average[none] and the np=2 eager tier."""
    hvd.init()
    t = torch.full((3,), 2.0)
    same = hvd.allreduce_(t, op=hvd.Sum, name="ip1.ar")
    assert same is t
    assert t.numpy().tolist() == [2.0, 2.0, 2.0]   # size 1: identity
    b = torch.full((2,), 7.0)
    hvd.broadcast_(b, root_rank=0, name="ip1.bc")
    assert b.numpy().tolist() == [7.0, 7.0]
    g1, g2 = torch.full((2,), 1.0), torch.full((2,), 5.0)
    outs = hvd.grouped_allreduce_([g1, g2], op=hvd.Sum, name="ip1.gar")
    assert outs[0] is g1 and outs[1] is g2
    c = hvd.allreduce(torch.full((4,), 3.0), op=hvd.Sum,
                      compression=hvd.Compression.fp16, name="ip1.comp")
    assert c.numpy().tolist() == [3.0, 3.0, 3.0, 3.0]


@pytest.mark.slow  # ISSUE 19 budget audit: 14s of np=2 torch spawn
# whose cross-rank math (average/sum over the native plane) tier-1
# already pins via test_two_rank_grad_average[none] and
# test_torch_differentiable_collectives[2]; the in-place-specific
# glue (aliasing, grouped identity, compression kwarg) moved to the
# single-process smoke above. Slow tier keeps the full two-rank
# in-place composition.
def test_inplace_ops_and_compression():
    results = run(_inplace_ops_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    for ar, b, g1, g2, c in results:
        assert ar == [3.0, 3.0, 3.0]          # 1 + 2
        assert b == [10.0, 10.0]              # rank 1's value
        assert g1 == [1.0, 1.0]               # 0 + 1
        assert g2 == [11.0, 11.0]             # 5 + 6
        assert c == [3.0, 3.0, 3.0, 3.0]


def _inplace_param_worker():
    import torch
    import horovod_tpu.torch as hvd

    hvd.init()
    p = torch.nn.Parameter(torch.full((3,), float(hvd.rank() + 1)))
    hvd.broadcast_(p, root_rank=0, name="ip.param")  # requires_grad leaf
    out = p.detach().numpy().tolist()
    hvd.shutdown()
    return out


# In-place broadcast onto live parameters is already pinned from two
# sides: broadcast_parameters semantics by
# test_broadcast_parameters_and_optimizer_state_nonzero_root (slow)
# and the in-place op family by test_inplace_ops_single_process
# (tier-1) + test_inplace_ops_and_compression (slow) — this variant's
# 2x-torch-spawn cost rides the slow tier (budget).
@pytest.mark.slow
def test_inplace_on_parameters():
    results = run(_inplace_param_worker, np=2, env=_WORKER_ENV,
                  start_timeout=90)
    for out in results:
        assert out == [1.0, 1.0, 1.0]  # rank 0's value everywhere
