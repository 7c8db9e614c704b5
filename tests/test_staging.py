"""Staging by content (``repro.core.engine_jax._stage_x`` and
``_stage_allowed``): X goes to the device as its non-zeros and ``allowed``
as bits, and both are expanded there bit-for-bit into the padded f32 X and
padded bool mask a dense upload gives.  Also: whole grant sequences equal
the dense staging's on every fused policy, and a growing support within a
shape bucket lowers no new program."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import engine_jax, tracing  # noqa: E402
from repro.core.engine_jax import _bucket, _pad, _put  # noqa: E402


def _dense_x(X, Np, Jp):
    """The dense staging: pad in f64, cast to f32, upload every cell."""
    X = np.asarray(X, np.float64)
    return _put(_pad(_pad(X, Np, 0, 0.0), Jp, 1, 0.0), np.float32)


def _dense_allowed(allowed, Np, Jp):
    return _put(_pad(_pad(np.asarray(allowed, bool), Np, 0, False),
                     Jp, 1, False))


def _support(kind, N, J, rng):
    X = np.zeros((N, J))
    if kind == "empty":
        return X
    if kind == "dense":
        return rng.integers(0, 4, size=(N, J)).astype(float)
    cells = rng.choice(N * J, size=min(N * J, 3 * N), replace=False)
    X.flat[cells] = rng.integers(1, 9, size=cells.size)
    if kind == "nan":
        X.flat[cells[::4]] = np.nan
        X.flat[cells[1]] = 0.1            # not exact in f32
    return X


def _bits(a):
    """The bit patterns of an f32 array (NaN payloads included)."""
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("shape", [(5, 13), (37, 101), (8, 16)])
@pytest.mark.parametrize("kind", ["empty", "sparse", "nan", "dense"])
@pytest.mark.parametrize("chunk", [None, 7])
def test_staged_x_equals_the_dense_padding(monkeypatch, shape, kind, chunk):
    if chunk is not None:
        monkeypatch.setattr(engine_jax, "STAGE_CHUNK", chunk)
    N, J = shape
    Np, Jp = _bucket(N), _bucket(J)
    X = _support(kind, N, J, np.random.default_rng(N * J))
    got = engine_jax._stage_x(X, Np, Jp)
    assert got.shape == (Np, Jp) and got.dtype == np.float32
    np.testing.assert_array_equal(_bits(got), _bits(_dense_x(X, Np, Jp)))


@pytest.mark.parametrize("shape", [(5, 13), (37, 101), (8, 16), (3, 8)])
@pytest.mark.parametrize("kind", ["all", "none", "random"])
def test_staged_allowed_equals_the_dense_padding(shape, kind):
    N, J = shape
    Np, Jp = _bucket(N), _bucket(J)
    rng = np.random.default_rng(N + J)
    allowed = {"all": np.ones((N, J), bool), "none": np.zeros((N, J), bool),
               "random": rng.random((N, J)) < 0.5}[kind]
    got = engine_jax._stage_allowed(allowed, Np, Jp)
    assert got.shape == (Np, Jp) and got.dtype == np.bool_
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_dense_allowed(allowed, Np, Jp)))


def _case(seed, integer=False):
    """A small cluster with executors already placed (so X has a support),
    N and J not powers of two, J not a multiple of 8, some placements
    refused."""
    rng = np.random.default_rng(seed)
    N, J = 7, 13
    C = rng.integers(24, 64, size=(J, 2)).astype(float)
    D = rng.integers(1, 5, size=(N, 2)).astype(float)
    if not integer:
        D = D / 2
    X = np.zeros((N, J))
    cells = rng.choice(N * J, size=12, replace=False)
    X.flat[cells] = 1.0
    allowed = rng.random((N, J)) < 0.8
    allowed.flat[cells] = True
    FREE = C - X.T @ D
    wanted = X.sum(axis=1) + rng.integers(2, 12, size=N)
    return dict(X=X, D=D, C=C, FREE=FREE, phi=np.ones(N), allowed=allowed,
                wanted=wanted, true_demands=D)


EPOCHS = [
    ("rpsdsf", "pooled", {}),
    ("drf", "pooled", {"max_steps_cap": 16}),
    ("drf", "rrr", {}),
    ("drf", "rrr", {"_donate": True, "_perm_rows": 1}),
    ("rpsdsf", "rrr", {"_donate": True, "_perm_rows": 1,
                       "max_steps_cap": 16}),
    ("drf", "bestfit", {}),
]


@pytest.mark.parametrize("crit,policy,kw", EPOCHS)
@pytest.mark.parametrize("chunk", [None, 4])
@pytest.mark.parametrize("seed", [0, 1])
def test_grant_sequences_equal_the_dense_staging(monkeypatch, crit, policy,
                                                 kw, chunk, seed):
    """The whole epoch, chained and grow-and-replay dispatches included,
    grants the same sequence with staging by content as with the dense
    staging, and hands the epoch program the same X and mask."""
    case = _case(seed, integer=policy == "bestfit")
    handed = []
    real = engine_jax._executable

    def spy(fn, args, static):
        handed.append((np.asarray(args[0]), np.asarray(args[7])))
        return real(fn, args, static)

    def run():
        handed.clear()
        seq = engine_jax.run_epoch(crit, policy,
                                   rng=np.random.default_rng(seed),
                                   **case, **kw)
        return seq, [(_bits(x), a) for x, a in handed]

    monkeypatch.setattr(engine_jax, "_executable", spy)
    if chunk is not None:
        monkeypatch.setattr(engine_jax, "STAGE_CHUNK", chunk)
    seq, inputs = run()
    with monkeypatch.context() as m:
        m.setattr(engine_jax, "_stage_x", _dense_x)
        m.setattr(engine_jax, "_stage_allowed", _dense_allowed)
        seq_dense, inputs_dense = run()
    assert len(seq) > 10 and seq == seq_dense
    assert len(inputs) == len(inputs_dense) >= 1
    for (x, a), (xd, ad) in zip(inputs, inputs_dense):
        np.testing.assert_array_equal(x, xd)
        np.testing.assert_array_equal(a, ad)
    if kw.get("_perm_rows"):
        assert len(inputs) > 1              # the stack grew and replayed


@pytest.mark.parametrize("chunk", [None, 16])
def test_a_growing_support_lowers_no_new_program(monkeypatch, chunk):
    """Two epochs of one padded shape whose supports lie in different
    power-of-two sizes (and, with a small chunk, need different numbers of
    chunks): the second lowers no program."""
    if chunk is not None:
        monkeypatch.setattr(engine_jax, "STAGE_CHUNK", chunk)
    monkeypatch.setattr(tracing, "RECORDER", tracing.Recorder())
    N, J = 12, 20
    rng = np.random.default_rng(5)
    C = np.full((J, 2), 64.0)
    D = np.ones((N, 2))

    def epoch(nnz):
        X = np.zeros((N, J))
        X.flat[rng.choice(N * J, size=nnz, replace=False)] = 1.0
        engine_jax.run_epoch(
            "rpsdsf", "pooled", X=X, D=D, C=C, FREE=C - X.T @ D,
            phi=np.ones(N), allowed=np.ones((N, J), bool),
            wanted=X.sum(axis=1) + 2, true_demands=D)
        return tracing.totals().get("engine_jax.staged_nonzeros", 0)

    assert epoch(3) == 3
    lowered = tracing.totals().get("jax.lowerings", 0)
    assert epoch(40) == 43
    assert tracing.totals().get("jax.lowerings", 0) == lowered
