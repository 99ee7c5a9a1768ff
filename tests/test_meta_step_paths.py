"""Execution-path equivalence for the meta step.

vmap / scan / chunked / sharded client axes (incl. non-divisor chunk
sizes) of the tree reference and of the flat pipeline (the fused
client-plane inner loop, all four algorithms, xla and pallas_interpret
kernels) must all produce the same φ and the same weighted metrics
after a round. Also covers the fused inner-update plane kernel (values
and custom VJP), the fused outer-Adam and weighted-aggregation kernels
against their jnp oracles, FlatPlane pack/unpack round-tripping, and
bit-identity of the ``adapt`` deployment path between the tree and
packed inner loops. None of this needs the optional `hypothesis`
dependency, so kernel equivalence stays covered even when
test_kernels_meta_update is skipped.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_algorithm
from repro.core.fedmeta import (federated_meta_step, init_packed_state,
                                make_packed_meta_train_step)
from repro.kernels.meta_update import ops as mu_ops
from repro.kernels.meta_update.aggregate import (weighted_aggregate_flat,
                                                 weighted_aggregate_ref)
from repro.optim import adam, sgd
from repro.optim.fused_adam import adam_flat_update
from repro.sharding.context import make_mesh
from repro.utils.flat import ALIGN, FlatPlane, plane_for


def quad_loss(params, batch):
    return 0.5 * jnp.sum(jnp.square(params["w"] - batch))


def quad_eval(params, batch):
    return quad_loss(params, batch), {"accuracy": jnp.zeros(())}


def _one_device_mesh():
    """shard_map runs unchanged on a 1-device mesh, so the sharded axis
    (padding, psum, local aggregation) is exercised on any host; the CI
    multi-device job re-runs this file with 4 forced host devices."""
    return make_mesh((jax.device_count(),), ("clients",))


def _make_round(rng, algo_name, m=5):
    theta = {"w": jnp.asarray(rng.normal(0, 1, (7,)), jnp.float32),
             "b": jnp.asarray(rng.normal(0, 1, (3,)), jnp.float32)}
    sup = jnp.asarray(rng.normal(0, 1, (m, 7)), jnp.float32)
    qry = jnp.asarray(rng.normal(0, 1, (m, 7)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 3.0, (m,)), jnp.float32)
    algo = make_algorithm(algo_name, quad2_loss, quad2_eval, inner_lr=0.1,
                          inner_steps=2)
    phi = algo.init_state(jax.random.PRNGKey(0), lambda k: theta)
    return algo, phi, sup, qry, w


def quad2_loss(params, batch):
    return (0.5 * jnp.sum(jnp.square(params["w"] - batch))
            + 0.1 * jnp.sum(params["b"] * batch[:3].sum()))


def quad2_eval(params, batch):
    return quad2_loss(params, batch), {"accuracy": jnp.zeros(())}


def _assert_phi_close(out_phi, ref_phi):
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        out_phi, ref_phi)


@pytest.fixture
def round_setup(rng):
    m = 5
    theta = {"w": jnp.asarray(rng.normal(0, 1, (7,)), jnp.float32)}
    sup = jnp.asarray(rng.normal(0, 1, (m, 7)), jnp.float32)
    qry = jnp.asarray(rng.normal(0, 1, (m, 7)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 3.0, (m,)), jnp.float32)
    algo = make_algorithm("meta-sgd", quad_loss, quad_eval, inner_lr=0.1)
    phi = algo.init_state(jax.random.PRNGKey(0), lambda k: theta)
    return algo, phi, sup, qry, w


# chunk sizes: divisor, non-divisor, and chunk > m (single padded chunk)
@pytest.mark.parametrize("axis,chunk", [
    ("scan", None), ("chunked", 1), ("chunked", 2), ("chunked", 3),
    ("chunked", 5), ("chunked", 8),
])
def test_client_axis_equivalence(round_setup, axis, chunk):
    algo, phi, sup, qry, w = round_setup
    opt = adam(1e-2)
    ref_phi, _, ref_met = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    out_phi, _, out_met = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis=axis,
        client_chunk=chunk)
    for k in ("theta", "alpha"):
        np.testing.assert_allclose(np.asarray(out_phi[k]["w"]),
                                   np.asarray(ref_phi[k]["w"]),
                                   rtol=1e-5, atol=1e-6)
    # every path reports the same weighted metrics (scan used to take an
    # unweighted mean)
    np.testing.assert_allclose(float(out_met["query_loss"]),
                               float(ref_met["query_loss"]), rtol=1e-5)


@pytest.mark.parametrize("axis,chunk", [
    ("vmap", None), ("scan", None), ("chunked", 2), ("chunked", 3),
])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_packed_plane_matches_tree(round_setup, axis, chunk, impl):
    algo, phi, sup, qry, w = round_setup
    opt = adam(1e-2)
    ref_phi, _, ref_met = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    plane = plane_for(phi)
    step = make_packed_meta_train_step(
        algo, opt, plane, client_axis=axis, client_chunk=chunk, impl=impl)
    state, met = step(init_packed_state(opt, plane, phi), sup, qry, w)
    out_phi = plane.unpack(state["phi"])
    for k in ("theta", "alpha"):
        np.testing.assert_allclose(np.asarray(out_phi[k]["w"]),
                                   np.asarray(ref_phi[k]["w"]),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(met["query_loss"]),
                               float(ref_met["query_loss"]), rtol=1e-5)


@pytest.mark.parametrize("setup", ["meta-sgd", "fomaml"])
def test_packed_bf16_block_close_to_f32(request, rng, setup):
    """The reduced-precision gradient block tracks the exact pipeline to
    bf16 tolerance (G rows cast before aggregation, f32 accumulation),
    on the single-leaf Meta-SGD round and on a two-leaf FOMAML one."""
    algo, phi, sup, qry, w = (request.getfixturevalue("round_setup")
                              if setup == "meta-sgd"
                              else _make_round(rng, "fomaml"))
    opt = adam(1e-2)
    ref_phi, _, _ = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    plane = plane_for(phi)
    step = make_packed_meta_train_step(algo, opt, plane,
                                       block_dtype=jnp.bfloat16)
    state, _ = step(init_packed_state(opt, plane, phi), sup, qry, w)
    out_phi = plane.unpack(state["phi"])
    np.testing.assert_allclose(np.asarray(out_phi["theta"]["w"]),
                               np.asarray(ref_phi["theta"]["w"]),
                               rtol=5e-2, atol=5e-3)


def test_packed_plane_non_adam_falls_back(round_setup):
    """Non-Adam outer optimizers run on the plane via the generic path."""
    algo, phi, sup, qry, w = round_setup
    opt = sgd(0.5, momentum=0.9)
    ref_phi, _, _ = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    plane = plane_for(phi)
    step = make_packed_meta_train_step(algo, opt, plane)
    state, _ = step(init_packed_state(opt, plane, phi), sup, qry, w)
    out_phi = plane.unpack(state["phi"])
    np.testing.assert_allclose(np.asarray(out_phi["theta"]["w"]),
                               np.asarray(ref_phi["theta"]["w"]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_fused_adam_kernel_matches_xla(rng, wd):
    N = 2 * ALIGN
    phi = jnp.asarray(rng.normal(0, 1, (N,)), jnp.float32)
    g = jnp.asarray(rng.normal(0, 1, (N,)), jnp.float32)
    m = jnp.asarray(rng.normal(0, 0.1, (N,)), jnp.float32)
    v = jnp.asarray(np.abs(rng.normal(0, 0.1, (N,))), jnp.float32)
    step = jnp.asarray(3, jnp.int32)
    ref = adam_flat_update(phi, g, m, v, step, lr=1e-3, wd=wd, impl="xla")
    out = adam_flat_update(phi, g, m, v, step, lr=1e-3, wd=wd,
                           impl="pallas_interpret")
    for r, o, name in zip(ref, out, ("phi", "m", "v", "step")):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-6, atol=1e-7, err_msg=name)


def test_fused_adam_multi_step_bias_correction(rng):
    """Several fused steps track the per-leaf tree Adam exactly."""
    N = ALIGN
    tree = {"a": jnp.asarray(rng.normal(0, 1, (300,)), jnp.float32),
            "b": jnp.asarray(rng.normal(0, 1, (20, 30)), jnp.float32)}
    plane = plane_for(tree)
    assert plane.n_padded == N
    opt = adam(3e-3)
    tree_state = opt.init(tree)
    flat = plane.pack(tree)
    m = v = jnp.zeros((N,), jnp.float32)
    step = jnp.zeros((), jnp.int32)
    for t in range(4):
        g_tree = jax.tree.map(
            lambda x: jnp.asarray(np.random.RandomState(t).normal(
                0, 1, x.shape), jnp.float32), tree)
        tree_out, tree_state = opt.update(tree_out if t else tree,
                                          g_tree, tree_state)
        flat, m, v, step = adam_flat_update(
            flat, plane.pack(g_tree), m, v, step, lr=3e-3, impl="xla")
    unpacked = plane.unpack(flat)
    for k in tree:
        np.testing.assert_allclose(np.asarray(unpacked[k]),
                                   np.asarray(tree_out[k]),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("m", [1, 3, 16])
def test_weighted_aggregation_kernel_matches_ref(rng, m):
    N = 2 * ALIGN
    gs = jnp.asarray(rng.normal(0, 1, (m, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(0, 1, (m,)), jnp.float32)
    ref = weighted_aggregate_ref(gs, w)
    out = weighted_aggregate_flat(gs, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_flat_plane_roundtrip(rng):
    tree = {"w": jnp.asarray(rng.normal(0, 1, (13, 7)), jnp.float32),
            "b": jnp.asarray(rng.normal(0, 1, (11,)), jnp.bfloat16),
            "s": jnp.asarray(1.5, jnp.float32)}
    plane = FlatPlane.from_tree(tree)
    assert plane.n_padded % ALIGN == 0
    out = plane.unpack(plane.pack(tree))
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        assert out[k].shape == tree[k].shape
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32), np.asarray(tree[k], np.float32),
            rtol=1e-2 if tree[k].dtype == jnp.bfloat16 else 1e-7)
    # batch pack
    batch = jax.tree.map(lambda x: jnp.stack([x, x + 1]), tree)
    packed = jax.vmap(plane.pack)(batch)
    assert packed.shape == (2, plane.n_padded)
    np.testing.assert_allclose(np.asarray(packed[0]),
                               np.asarray(plane.pack(tree)), rtol=1e-6)


def test_plane_for_is_cached(rng):
    t1 = {"w": jnp.zeros((4, 4), jnp.float32)}
    t2 = {"w": jnp.ones((4, 4), jnp.float32)}
    assert plane_for(t1) is plane_for(t2)


def test_unpack_ad_matches_unpack_and_grad(rng):
    tree = {"w": jnp.asarray(rng.normal(0, 1, (13, 7)), jnp.float32),
            "b": jnp.asarray(rng.normal(0, 1, (11,)), jnp.float32)}
    plane = plane_for(tree)
    flat = plane.pack(tree)
    out = plane.unpack_ad(flat)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(plane.unpack(flat)[k]))

    def f_ad(x):
        t = plane.unpack_ad(x)
        return jnp.sum(jnp.sin(t["w"])) + jnp.sum(t["b"] ** 2)

    def f_plain(x):
        t = plane.unpack(x)
        return jnp.sum(jnp.sin(t["w"])) + jnp.sum(t["b"] ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(f_ad)(flat)),
                               np.asarray(jax.grad(f_plain)(flat)),
                               rtol=1e-6, atol=1e-7)
    # second order (reverse-over-reverse) composes through the custom rule
    def meta(x):
        g = jax.grad(f_ad)(x)
        return jnp.sum(jnp.cos(plane.unpack_ad(x - 0.1 * g)["w"]))

    def meta_plain(x):
        g = jax.grad(f_plain)(x)
        return jnp.sum(jnp.cos(plane.unpack(x - 0.1 * g)["w"]))

    np.testing.assert_allclose(np.asarray(jax.grad(meta)(flat)),
                               np.asarray(jax.grad(meta_plain)(flat)),
                               rtol=1e-5, atol=1e-6)


# ---- fused inner-update plane kernel ------------------------------------

@pytest.mark.parametrize("alpha_kind", ["scalar", "shared", "per_client"])
def test_inner_update_plane_kernel_matches_ref(rng, alpha_kind):
    C, N = 3, 2 * ALIGN
    T = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    G = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    alpha = {"scalar": 0.05,
             "shared": jnp.asarray(rng.uniform(0, 0.1, (N,)), jnp.float32),
             "per_client": jnp.asarray(rng.uniform(0, 0.1, (C, N)),
                                       jnp.float32)}[alpha_kind]
    ref = mu_ops.inner_update(T, alpha, G, impl="xla")
    out = mu_ops.inner_update(T, alpha, G, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("alpha_kind", ["scalar", "shared", "per_client"])
def test_inner_update_plane_custom_vjp(rng, alpha_kind):
    """The kernel's custom VJP matches autodiff through the jnp oracle —
    this is what second-order MAML/Meta-SGD differentiate through."""
    C, N = 2, ALIGN
    T = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    G = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    alpha = {"scalar": 0.07,
             "shared": jnp.asarray(rng.uniform(0, 0.1, (N,)), jnp.float32),
             "per_client": jnp.asarray(rng.uniform(0, 0.1, (C, N)),
                                       jnp.float32)}[alpha_kind]

    def make_f(impl):
        def f(*args):
            if alpha_kind == "scalar":
                t, g = args
                return jnp.sum(jnp.sin(
                    mu_ops.inner_update(t, alpha, g, impl=impl)))
            t, a, g = args
            return jnp.sum(jnp.sin(mu_ops.inner_update(t, a, g, impl=impl)))
        return f

    args = (T, G) if alpha_kind == "scalar" else (T, alpha, G)
    argnums = tuple(range(len(args)))
    ref = jax.grad(make_f("xla"), argnums=argnums)(*args)
    out = jax.grad(make_f("pallas_interpret"), argnums=argnums)(*args)
    for r, o in zip(ref, out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


# ---- client-plane inner loop & sharded axis -----------------------------

ALGOS = ["maml", "fomaml", "meta-sgd", "reptile"]


@pytest.mark.parametrize("algo_name", ALGOS)
@pytest.mark.parametrize("axis,chunk", [
    ("vmap", None), ("scan", None), ("chunked", 2), ("sharded", None),
])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_client_plane_matches_tree(rng, algo_name, axis, chunk, impl):
    """The fused flat inner loop reproduces the tree round for every
    algorithm, on every client axis, under both kernel impls."""
    algo, phi, sup, qry, w = _make_round(rng, algo_name)
    opt = adam(1e-2)
    ref_phi, _, ref_met = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    plane = plane_for(phi)
    step = make_packed_meta_train_step(
        algo, opt, plane, client_axis=axis, client_chunk=chunk, impl=impl,
        mesh=_one_device_mesh())
    state, met = step(init_packed_state(opt, plane, phi), sup, qry, w)
    _assert_phi_close(plane.unpack(state["phi"]), ref_phi)
    np.testing.assert_allclose(float(met["query_loss"]),
                               float(ref_met["query_loss"]), rtol=1e-5)


@pytest.mark.parametrize("pipeline", ["tree", "client_plane"])
def test_sharded_axis_matches_vmap(rng, pipeline):
    """client_axis="sharded" (shard_map + psum-reduced partials) produces
    the identical round for every pipeline, including a non-divisor
    client count (zero-weight padding)."""
    m = 5                                    # never divisible by >1 devs
    algo, phi, sup, qry, w = _make_round(rng, "meta-sgd", m=m)
    opt = adam(1e-2)
    ref_phi, _, ref_met = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    mesh = _one_device_mesh()
    if pipeline == "tree":
        out_phi, _, met = federated_meta_step(
            algo, opt, phi, opt.init(phi), sup, qry, w,
            client_axis="sharded", mesh=mesh)
    else:
        plane = plane_for(phi)
        step = make_packed_meta_train_step(
            algo, opt, plane, client_axis="sharded", mesh=mesh)
        state, met = step(init_packed_state(opt, plane, phi), sup, qry, w)
        out_phi = plane.unpack(state["phi"])
    _assert_phi_close(out_phi, ref_phi)
    np.testing.assert_allclose(float(met["query_loss"]),
                               float(ref_met["query_loss"]), rtol=1e-5)


def test_sharded_with_local_chunking(rng):
    """client_chunk composes with the sharded axis (scan of chunks inside
    each device's shard)."""
    algo, phi, sup, qry, w = _make_round(rng, "fomaml", m=6)
    opt = adam(1e-2)
    ref_phi, _, _ = federated_meta_step(
        algo, opt, phi, opt.init(phi), sup, qry, w, client_axis="vmap")
    plane = plane_for(phi)
    step = make_packed_meta_train_step(
        algo, opt, plane, client_axis="sharded", client_chunk=2,
        mesh=_one_device_mesh())
    state, _ = step(init_packed_state(opt, plane, phi), sup, qry, w)
    _assert_phi_close(plane.unpack(state["phi"]), ref_phi)


def test_trainer_and_step_agree_on_plane_composition():
    """A packed FederatedTrainer and make_packed_meta_train_step accept
    and refuse the same combinations of planes, client axes and
    aggregators (both apply fedmeta.check_plane_composition)."""
    import itertools

    from repro.federated.async_engine import StalenessConfig
    from repro.federated.faults import FaultConfig
    from repro.federated.privacy import DPConfig
    from repro.federated.server import FederatedTrainer
    from repro.kernels.meta_update.compress import CompressionConfig
    algo = make_algorithm("fomaml", quad_loss, quad_eval, inner_lr=0.1)
    phi = algo.init_state(jax.random.PRNGKey(0),
                          lambda k: {"w": jnp.zeros((7,), jnp.float32)})
    plane, opt = plane_for(phi), adam(1e-3)
    planes = {"staleness": StalenessConfig(),
              "faults": FaultConfig(dropout=0.25, seed=1),
              "compression": CompressionConfig("int8"), "dp": DPConfig()}

    def accepts(build):
        try:
            build()
        except ValueError:
            return False
        return True

    verdicts = []
    for axis, aggregator, *on in itertools.product(
            ("vmap", "chunked"), ("mean", "trimmed", "median"),
            *[(False, True)] * len(planes)):
        kw = {k: v for (k, v), used in zip(planes.items(), on) if used}
        kw.update(client_axis=axis, client_chunk=2, aggregator=aggregator)
        trainer_ok = accepts(lambda: FederatedTrainer(
            algo, opt, [], 4, support_frac=0.5, support_size=2,
            query_size=2, packed=True, **kw))
        step_ok = accepts(
            lambda: make_packed_meta_train_step(algo, opt, plane, **kw))
        assert trainer_ok == step_ok, kw
        verdicts.append(step_ok)
    assert 0 < sum(verdicts) < len(verdicts)


def test_metasgd_integer_seeds_differ():
    """Integer seeds must produce distinct α initializations (the seed
    used to be silently replaced by PRNGKey(0))."""
    algo = make_algorithm("meta-sgd", quad2_loss, quad2_eval, inner_lr=0.1)
    init = lambda k: {"w": jnp.zeros((7,), jnp.float32)}   # noqa: E731
    a0 = algo.init_state(0, init)["alpha"]["w"]
    a1 = algo.init_state(1, init)["alpha"]["w"]
    assert not np.array_equal(np.asarray(a0), np.asarray(a1))
    # int seed k and PRNGKey(k) agree
    a0k = algo.init_state(jax.random.PRNGKey(0), init)["alpha"]["w"]
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a0k))


# ---- deployment path: adapt must be bit-identical -----------------------

@pytest.mark.parametrize("algo_name", ALGOS)
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_adapt_packed_bit_identical(rng, algo_name, impl):
    """paper §3.2: the deployed adapted θ must be bit-identical between
    the tree inner loop and the packed/fused inner loop, for all four
    algorithms, with both inner loops under the same impl. (Comparing
    across impls is 1 ulp apart on CPU: XLA contracts θ − α∘g into an
    FMA whenever it compiles the expression as one program, while the
    eager per-leaf path rounds the product first.)"""
    algo, phi, sup, qry, w = _make_round(rng, algo_name)
    with mu_ops.use_impl(impl):
        theta_tree = algo.adapt(phi, sup[0], steps=3)
    theta_flat = algo.adapt_packed(phi, sup[0], steps=3, impl=impl)
    for k in theta_tree:
        np.testing.assert_array_equal(np.asarray(theta_tree[k]),
                                      np.asarray(theta_flat[k]),
                                      err_msg=f"{algo_name}/{impl}/{k}")


# ---- donation and staging ------------------------------------------------

def test_packed_step_donates_state(rng):
    """The jitted meta step consumes its state on every backend (the
    buffers update in place), so a caller must read what it needs from
    a state before stepping it."""
    algo, phi, sup, qry, w = _make_round(rng, "fomaml")
    plane = plane_for(phi)
    opt = adam(1e-3)
    step = make_packed_meta_train_step(algo, opt, plane)
    state = init_packed_state(opt, plane, phi)
    new_state, _ = step(state, sup, qry, w)
    assert state["phi"].is_deleted()
    assert not new_state["phi"].is_deleted()
    keep = make_packed_meta_train_step(algo, opt, plane, donate=False)
    state = init_packed_state(opt, plane, phi)
    keep(state, sup, qry, w)
    assert not state["phi"].is_deleted()


def test_sharded_axis_stages_inputs_over_the_mesh():
    """On the sharded client axis the trainer stages each round's
    client-axis arrays split over the mesh (one block of clients per
    device); other axes stage on the default device."""
    from jax.sharding import NamedSharding

    from repro.federated.server import FederatedTrainer
    algo = make_algorithm("fomaml", quad_loss, quad_eval, inner_lr=0.1)
    mesh = _one_device_mesh()
    n = mesh.shape["clients"]
    kw = dict(clients_per_round=2 * n, support_frac=0.5, support_size=2,
              query_size=2, packed=True)
    tr = FederatedTrainer(algo, adam(1e-3), [], client_axis="sharded",
                          mesh=mesh, **kw)
    staged = tr.placement()(np.zeros((2 * n, 3), np.float32))
    assert isinstance(staged.sharding, NamedSharding)
    assert staged.sharding.spec == jax.sharding.PartitionSpec("clients")
    assert staged.sharding.device_set == set(mesh.devices.flat)
    assert tr.placement()(np.float32(1.0)).shape == ()
    assert FederatedTrainer(algo, adam(1e-3), [], **kw).placement() \
        is jax.device_put


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "tree"])
def test_sharded_trainer_compiles_its_step_once(packed, tmp_path):
    """`run` puts its state where the sharded step returns it
    (replicated over the client mesh), so later rounds replay the first
    round's executable instead of compiling the step again — for a
    fresh state from `init` and for one loaded by `resume`."""
    from repro.core import classification_loss
    from repro.data.federated import ClientData
    from repro.federated.server import FederatedTrainer

    rng = np.random.RandomState(0)
    clients = [ClientData(rng.normal(0, 1, (12, 4)).astype(np.float32),
                          rng.randint(0, 2, (12,)).astype(np.int64))
               for _ in range(6)]

    def model_init(key):
        return {"w": jax.random.normal(key, (4, 2)) * 0.1,
                "b": jnp.zeros((2,))}

    loss_fn, eval_fn = classification_loss(lambda p, x: x @ p["w"] + p["b"])
    algo = make_algorithm("fomaml", loss_fn, eval_fn, inner_lr=0.05)
    mesh = _one_device_mesh()

    def trainer():
        return FederatedTrainer(
            algo, adam(1e-3), clients, 2 * mesh.size, support_frac=0.5,
            support_size=4, query_size=4, packed=packed,
            client_axis="sharded", mesh=mesh,
            checkpoint_every=2, checkpoint_dir=str(tmp_path))

    tr = trainer()
    state = tr.init(jax.random.PRNGKey(0), model_init)
    state = tr.run(state, 3)
    assert tr._step._cache_size() == 1
    assert len(tr.history) == 3

    tr = trainer()
    tr.init(jax.random.PRNGKey(0), model_init)
    state, start = tr.resume()
    assert start == 2
    tr.run(state, 4, start_round=start)
    assert tr._step._cache_size() == 1
    assert len(tr.history) == 4
