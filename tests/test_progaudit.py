"""Progaudit contract tier (ISSUE 15): the jaxpr-level auditor's
detectors (callbacks, f64 drift, collective-count fusion, donation
consumption, compiled temporaries under a ceiling) on synthetic
programs, and THE acceptance — the real hot-program registry (train
grads, ZeRO shard-apply, bucketed allreduce/reduce-scatter, the paged
decode step and prefill chunk, the fused spec window) audits clean on
the current tree."""

import jax
import jax.numpy as jnp
import pytest

from ptype_tpu import progaudit


# ------------------------------------------------------------ detectors


def test_clean_program_audits_clean():
    rep = progaudit.audit(lambda x: x * 2 + 1,
                          (jax.ShapeDtypeStruct((8,), jnp.float32),),
                          name="clean", expect_collectives=0)
    assert rep.ok and rep.collectives == {} and rep.eqns >= 2
    assert rep.raise_if_failed() is rep


def test_callback_in_program_is_flagged():
    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    rep = progaudit.audit(
        noisy, (jax.ShapeDtypeStruct((4,), jnp.float32),),
        name="noisy")
    assert not rep.ok and rep.callbacks, rep.to_dict()
    with pytest.raises(progaudit.AuditError, match="noisy"):
        rep.raise_if_failed()


def test_pure_callback_is_flagged():
    import numpy as np

    def hybrid(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x)

    rep = progaudit.audit(
        hybrid, (jax.ShapeDtypeStruct((4,), jnp.float32),))
    assert not rep.ok and "pure_callback" in rep.callbacks


def test_f64_drift_is_flagged_and_allow_f64_waives():
    def drift(x):
        return x.astype(jnp.float64).sum()

    with jax.enable_x64(True):
        rep = progaudit.audit(
            drift, (jax.ShapeDtypeStruct((4,), jnp.float32),),
            name="drift")
        waived = progaudit.audit(
            drift, (jax.ShapeDtypeStruct((4,), jnp.float32),),
            allow_f64=True)
    assert not rep.ok and rep.f64_sites, rep.to_dict()
    assert waived.ok


def test_unfused_collective_count_breaks_the_contract():
    """N per-leaf psums where the bucket plan says ONE — the un-fusion
    regression the launch-count invariant exists to catch."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(jax.devices(), ("data",))

    def per_leaf(a, b):
        return (jax.lax.psum(a, "data"), jax.lax.psum(b, "data"))

    fn = shard_map(per_leaf, mesh=mesh,
                   in_specs=(P("data"), P("data")),
                   out_specs=(P(), P()), check_vma=False)
    n = jax.device_count()
    avals = (jax.ShapeDtypeStruct((n, 4), jnp.float32),
             jax.ShapeDtypeStruct((n, 4), jnp.float32))
    rep = progaudit.audit(fn, avals, name="unfused",
                          expect_collectives=1)
    assert not rep.ok and rep.collectives.get("psum") == 2, \
        rep.to_dict()
    ok = progaudit.audit(fn, avals, expect_collectives={"psum": 2})
    assert ok.ok


def test_dropped_donation_is_flagged():
    """Donating a buffer no output can alias (shape mismatch) makes
    XLA drop the donation — the audit sees no marker in the lowering
    and flags the copy."""
    rep = progaudit.audit(
        lambda x: x.sum(),
        (jax.ShapeDtypeStruct((16,), jnp.float32),),
        name="dropped", donate_argnums=(0,))
    assert not rep.ok and rep.donated_consumed < rep.donated_expected
    assert any("donation" in p for p in rep.problems), rep.problems


def test_consumed_donation_passes():
    rep = progaudit.audit(
        lambda x: x * 2,
        (jax.ShapeDtypeStruct((16,), jnp.float32),),
        donate_argnums=(0,))
    assert rep.ok and rep.donated_consumed >= 1, rep.to_dict()


def test_temporaries_ceiling_compiles_and_compares():
    """A 256 KiB product under a 1 KiB ceiling is flagged, under a
    1 MiB ceiling it passes; with no ceiling nothing is compiled."""
    def outer_sum(x):
        return (jnp.outer(x, x) @ jnp.outer(x, x)).sum()

    args = (jax.ShapeDtypeStruct((256,), jnp.float32),)
    over = progaudit.audit(outer_sum, args, name="outer",
                           max_temp_bytes=1 << 10)
    assert not over.ok and over.temp_bytes >= 256 * 256 * 4
    assert any("temporaries" in p for p in over.problems)
    with pytest.raises(progaudit.AuditError, match="temporaries"):
        over.raise_if_failed()
    under = progaudit.audit(outer_sum, args, max_temp_bytes=1 << 20)
    assert under.ok and under.temp_bytes == over.temp_bytes
    assert under.to_dict()["max_temp_bytes"] == 1 << 20
    assert progaudit.audit(outer_sum, args).temp_bytes is None


@pytest.mark.parametrize("banks,ok", [("scanned", False),
                                      ("carried", True)])
def test_scanned_banks_keep_the_marker_and_break_the_ceiling(banks, ok):
    """The shape of the paged layer loop before ISSUE 26 and after: a
    donated stack of per-layer banks that a scan over layers updates a
    few rows of. As a scanned input and output the donation marker is
    in the lowering all the same, and the compiled program holds a
    whole copy; as the carry, indexed by layer, it holds none."""
    L, n, d = 4, 4096, 64
    bank = jax.ShapeDtypeStruct((L, n, d), jnp.float32)
    rows = jax.ShapeDtypeStruct((L, 8, d), jnp.float32)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32)

    def scanned(bank, rows, idx):
        def body(x, inp):
            b, r = inp
            b = b.at[idx].set(r)
            return x + b[idx].sum(), b

        return jax.lax.scan(body, 0.0, (bank, rows))

    def carried(bank, rows, idx):
        def body(carry, inp):
            x, b = carry
            l, r = inp
            b = b.at[l, idx].set(r)
            return (x + b[l, idx].sum(), b), None

        return jax.lax.scan(body, (0.0, bank), (jnp.arange(L), rows))[0]

    rep = progaudit.audit(
        {"scanned": scanned, "carried": carried}[banks],
        (bank, rows, idx), name=banks, donate_argnums=(0,),
        max_temp_bytes=L * n * d * 4)
    assert rep.donated_consumed == rep.donated_expected == 1
    assert rep.ok is ok, rep.to_dict()


# ------------------------------------------------------------- registry


def test_unknown_program_raises_keyerror():
    with pytest.raises(KeyError, match="no registered hot program"):
        progaudit.audit_registered("no.such.program")


def test_default_registry_covers_the_hot_programs():
    progaudit.register_default_programs()
    names = progaudit.registered()
    assert set(progaudit.DEFAULT_PROGRAMS) <= set(names)
    assert len(progaudit.DEFAULT_PROGRAMS) >= 5


def test_real_hot_programs_audit_clean():
    """THE acceptance (ISSUE 15): every registered hot program on the
    CURRENT tree traces with no callbacks, no f64, the pinned
    collective launch counts, and consumed donations."""
    progaudit.register_default_programs()
    reports = progaudit.audit_all(raise_on_failure=True)
    assert len(reports) >= 5
    # The specific contract points, pinned:
    assert reports["collectives.bucket_allreduce"].collectives == \
        {"psum": 1}
    assert reports["collectives.bucket_reduce_scatter"].collectives \
        == {"reduce_scatter": 1}
    assert reports["zero.shard_apply"].collectives == {"all_gather": 1}
    dec = reports["serve.decode_step"]
    assert dec.donated_consumed == dec.donated_expected == 2
    win = reports["serve.spec_window"]
    assert win.donated_consumed == win.donated_expected == 4
    assert win.collectives == {} and dec.collectives == {}
    pre = reports["serve.prefill_chunk"]
    assert pre.donated_consumed == pre.donated_expected == 2
    # The paged programs update their donated banks in place, and the
    # decode step's tile loop only reads them (ISSUE 29): all the
    # compiled temporaries together come to less than a quarter of one
    # bank (the window's: one draft bank).
    for rep in (dec, pre, win):
        assert 0 < rep.temp_bytes < rep.max_temp_bytes, rep.to_dict()


def test_hier_programs_pin_per_leg_launches():
    """ISSUE 18: the hierarchical programs' per-LEG launch pins. The
    allreduce lowers to exactly one launch per leg — inner
    reduce-scatter, ONE cross-domain psum (the only slow-leg launch),
    inner allgather; the scatter half is two reduce_scatter prims
    (psum_scatter lowers to reduce_scatter) and no gather. Any extra
    launch means a leg regressed to a flat collective and the
    1/N_inner slow-leg wire bound is gone."""
    progaudit.register_default_programs()
    ar = progaudit.audit_registered("collectives.hier_allreduce")
    ar.raise_if_failed()
    assert ar.collectives == {"reduce_scatter": 1, "psum": 1,
                              "all_gather": 1}
    rs = progaudit.audit_registered("collectives.hier_reduce_scatter")
    rs.raise_if_failed()
    assert rs.collectives == {"reduce_scatter": 2}


# ----------------------------------------------- ZeRO ladder programs


def test_zero_ladder_programs_pin_their_collectives():
    """The ladder's launch-count contract (ISSUE 17), pinned program
    by program: ZeRO-2 reduce-scatters each grad bucket ONCE and never
    allgathers grads; ZeRO-3 allgathers each param bucket ONCE
    just-in-time and its shard-local apply launches NOTHING (and eats
    its donated param/moment flats)."""
    progaudit.register_default_programs()
    reports = progaudit.audit_all(raise_on_failure=True)
    assert reports["zero1.shard_apply"].collectives == \
        {"all_gather": 1}
    rs = reports["zero2.grad_reduce_scatter"]
    assert rs.collectives.get("reduce_scatter") == 1
    assert rs.collectives.get("all_gather", 0) == 0
    assert reports["zero3.param_gather"].collectives == \
        {"all_gather": 1}
    ap3 = reports["zero3.shard_apply"]
    assert ap3.collectives == {}
    assert ap3.donated_consumed == ap3.donated_expected == 3


def test_split_bucket_two_reduce_scatters_breaks_the_pin():
    """Synthetic un-fusion: the SAME flat reduced as two half-bucket
    reduce-scatters — the per-prim pin {reduce_scatter: 1} catches
    what a total-count-only check would if it summed to the same."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(jax.devices(), ("data",))
    n = jax.device_count()

    def split(x):
        h = x.shape[-1] // 2
        a = jax.lax.psum_scatter(x[..., :h], "data",
                                 scatter_dimension=0, tiled=True)
        b = jax.lax.psum_scatter(x[..., h:], "data",
                                 scatter_dimension=0, tiled=True)
        return jnp.concatenate([a, b])

    fn = shard_map(split, mesh=mesh, in_specs=(P("data"),),
                   out_specs=P("data"), check_vma=False)
    rep = progaudit.audit(
        fn, (jax.ShapeDtypeStruct((n * 8, 16), jnp.float32),),
        name="split-rs",
        expect_collectives={"reduce_scatter": 1})
    assert not rep.ok, rep.to_dict()
    assert rep.collectives.get("reduce_scatter") == 2


def test_sneaky_grad_allgather_breaks_the_zero2_pin():
    """Synthetic regression: a reduce-scatter that then allgathers the
    shard back (defeating ZeRO-2's whole point) trips the explicit
    {all_gather: 0} pin even though reduce_scatter still counts 1."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(jax.devices(), ("data",))
    n = jax.device_count()

    def rs_then_gather(x):
        s = jax.lax.psum_scatter(x, "data", scatter_dimension=0,
                                 tiled=True)
        return jax.lax.all_gather(s, "data", tiled=True)

    fn = shard_map(rs_then_gather, mesh=mesh, in_specs=(P("data"),),
                   out_specs=P("data"), check_vma=False)
    rep = progaudit.audit(
        fn, (jax.ShapeDtypeStruct((n * 8,), jnp.float32),),
        name="sneaky-gather",
        expect_collectives={"reduce_scatter": 1, "all_gather": 0})
    assert not rep.ok, rep.to_dict()
    assert rep.collectives.get("all_gather") == 1


def test_per_leaf_param_gathers_break_the_zero3_pin():
    """Synthetic un-fusion for the just-in-time gather: one allgather
    per leaf instead of one per flat bucket."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(jax.devices(), ("data",))
    n = jax.device_count()

    def per_leaf(a, b):
        return (jax.lax.all_gather(a, "data", tiled=True),
                jax.lax.all_gather(b, "data", tiled=True))

    fn = shard_map(per_leaf, mesh=mesh,
                   in_specs=(P("data"), P("data")),
                   out_specs=(P(), P()), check_vma=False)
    avals = (jax.ShapeDtypeStruct((n * 4,), jnp.float32),
             jax.ShapeDtypeStruct((n * 2,), jnp.float32))
    rep = progaudit.audit(fn, avals, name="per-leaf-gather",
                          expect_collectives={"all_gather": 1})
    assert not rep.ok and rep.collectives.get("all_gather") == 2, \
        rep.to_dict()
