"""mxshard tests: the SPMD partition model and the three passes it
powers (sharding-soundness, replication-soundness, donation-soundness),
plus the ISSUE-19 satellites (linter-source cache key glob,
--profile-passes).

Pure-AST + stdlib: no jax import, so the whole file costs a few
seconds (tier-1 budget discipline — ROADMAP.md).
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.mxlint import PASSES, lint_paths, lint_sources  # noqa: E402
from tools.mxlint.cache import cache_key                   # noqa: E402

SPMD_PASSES = ["sharding-soundness", "replication-soundness",
               "donation-soundness"]

HDR = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.experimental.shard_map import shard_map
"""


def run(src, select=None, path="mxnet_tpu/fixture.py", extra=None):
    sources = {path: textwrap.dedent(HDR) + textwrap.dedent(src)}
    for p, s in (extra or {}).items():
        sources[p] = textwrap.dedent(s)
    return lint_sources(sources, select=select)


def ids(issues):
    return [i.pass_id for i in issues]


def test_catalogue_has_twentytwo_passes():
    assert len(PASSES) == 22
    for pid in SPMD_PASSES:
        assert pid in PASSES


# ========================================================= pass 17: specs
def test_unknown_axis_on_resolved_mesh_fires():
    issues = run("""
        def f(devs, body):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            g = shard_map(body, mesh, in_specs=(P("model"),),
                          out_specs=P("model"))
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "'model'" in issues[0].message
    assert "['dp', 'tp']" in issues[0].message


def test_known_axes_stay_quiet():
    issues = run("""
        def f(devs, body):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            g = shard_map(body, mesh, in_specs=(P("dp"), P("tp")),
                          out_specs=P(("dp", "tp")))
    """, select=["sharding-soundness"])
    assert issues == []


def test_duplicate_axis_in_one_spec_fires():
    issues = run("""
        def f(devs, body):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            s = NamedSharding(mesh, P("tp", "tp"))
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "more than one dim" in issues[0].message


def test_unresolved_mesh_checks_against_axis_universe():
    # the mesh is a runtime parameter, but SOME mesh in the project
    # names its axes — a spec axis outside every literal axis set flags
    issues = run("""
        MESH = Mesh(np.array([0]).reshape(1, 1), axis_names=("dp", "tp"))

        def f(mesh, body):
            g = shard_map(body, mesh, in_specs=(P("bogus"),),
                          out_specs=P("bogus"))
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "any mesh constructed in this project" in issues[0].message


def test_replica_mesh_helper_resolves_axis_names():
    # placement.replica_mesh-style maker: axis_names=("dp", axis_name)
    # resolves through the helper param default — strict checking
    issues = run("""
        def replica_mesh(group, axis_name="tp"):
            return Mesh(np.array(group, dtype=object)
                        .reshape(1, len(group)),
                        axis_names=("dp", axis_name))

        def f(group, body):
            mesh = replica_mesh(group)
            s = NamedSharding(mesh, P("model"))
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "['dp', 'tp']" in issues[0].message


def test_replica_mesh_call_site_axis_name_override():
    # a literal call-site kwarg beats the helper default
    issues = run("""
        def replica_mesh(group, axis_name="tp"):
            return Mesh(np.array(group, dtype=object)
                        .reshape(1, len(group)),
                        axis_names=("dp", axis_name))

        def f(group, body):
            mesh = replica_mesh(group, axis_name="model")
            s = NamedSharding(mesh, P("model"))
    """, select=["sharding-soundness"])
    assert issues == []


def test_divisibility_fires_on_concrete_mismatch():
    # dim 12 sharded over extent-8 tp: 12/8 is a symbol-free fraction
    issues = run("""
        def body(x):
            return x

        def f(devs):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            g = shard_map(body, mesh, in_specs=(P("tp", None),),
                          out_specs=P("tp", None))
            y = jnp.ones((12, 4))
            return g(y)
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "not divisible" in issues[0].message
    assert "extent 8" in issues[0].message


def test_divisibility_quiet_when_divisible_or_symbolic():
    issues = run("""
        def body(x):
            return x

        def f(devs, z):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            g = shard_map(body, mesh, in_specs=(P("tp", None),),
                          out_specs=P("tp", None))
            ok = jnp.ones((16, 4))          # 16 % 8 == 0: provable
            g(ok)
            B, D = z.shape                  # symbolic: undecidable
            g(z)
    """, select=["sharding-soundness"])
    assert issues == []


def test_rank_overflow_fires():
    issues = run("""
        def f(devs):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            x = jnp.ones((4, 4))
            y = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp", None, None)))
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "rank 2" in issues[0].message


def test_spec_built_in_helper_carries_witness_chain():
    issues = run("""
        def make_specs():
            return (P("bogus"),)

        def f(devs, body):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            g = shard_map(body, mesh, in_specs=make_specs(),
                          out_specs=P())
    """, select=["sharding-soundness"])
    assert ids(issues) == ["sharding-soundness"]
    assert "via make_specs (mxnet_tpu/fixture.py:" in issues[0].message


def test_sharding_suppression_is_honored():
    issues = run("""
        def f(devs, body):
            mesh = Mesh(np.array(devs).reshape(1, 8),
                        axis_names=("dp", "tp"))
            # mxlint: disable=sharding-soundness (transition mesh)
            g = shard_map(body, mesh, in_specs=(P("model"),),
                          out_specs=P("model"))
    """, select=["sharding-soundness"])
    assert issues == []


# ================================================== pass 18: replication
def test_p_out_spec_on_raw_shard_fires():
    issues = run("""
        def body(x):
            return x

        def f(mesh, x):
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=P())
            return g(x)
    """, select=["replication-soundness"])
    assert ids(issues) == ["replication-soundness"]
    assert "per-device shard" in issues[0].message


def test_reduced_output_is_quiet():
    issues = run("""
        def body(x):
            return lax.psum(x, "dp")

        def f(mesh, x):
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=P())
            return g(x)
    """, select=["replication-soundness"])
    assert issues == []


def test_tuple_alignment_flags_only_the_shard_element():
    issues = run("""
        def body(x):
            s = lax.pmean(x, "dp")
            return s, x

        def f(mesh, x):
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=(P(), P()))
            return g(x)
    """, select=["replication-soundness"])
    assert ids(issues) == ["replication-soundness"]
    assert "out_specs[1]" in issues[0].message


def test_sharded_out_spec_accepts_the_shard():
    issues = run("""
        def body(x):
            s = lax.pmean(x, "dp")
            return s, x

        def f(mesh, x):
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=(P(), P("dp")))
            return g(x)
    """, select=["replication-soundness"])
    assert issues == []


def test_interprocedural_helper_states_per_element():
    # the quantize.allreduce shape: a helper returning
    # (uniform, per-device) — only the per-device element flags
    issues = run("""
        def allreduce(x):
            g = lax.all_gather(x, "dp")
            total = jnp.sum(g, axis=0)
            return total, x

        def body(x):
            out, res = allreduce(x)
            return out, res

        def f(mesh, x):
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=(P(), P()))
            return g(x)
    """, select=["replication-soundness"])
    assert ids(issues) == ["replication-soundness"]
    assert "out_specs[1]" in issues[0].message


def test_shuffling_collective_does_not_wash():
    # ppermute results still differ per device — P() stays wrong
    issues = run("""
        def body(x):
            y = lax.ppermute(x, "dp", perm=[(0, 1), (1, 0)])
            return y

        def f(mesh, x):
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=P())
            return g(x)
    """, select=["replication-soundness"])
    assert ids(issues) == ["replication-soundness"]


def test_lambda_body_and_unchecked_variant():
    issues = run("""
        def f(mesh, x):
            g = shard_map(lambda v: v, mesh,
                          in_specs=(P("dp"),),
                          out_specs=P(), check_vma=False)
            h = shard_map(lambda v: lax.psum(v, "dp"), mesh,
                          in_specs=(P("dp"),),
                          out_specs=P(), check_vma=False)
            return g(x), h(x)
    """, select=["replication-soundness"])
    assert ids(issues) == ["replication-soundness"]


def test_replication_suppression_is_honored():
    issues = run("""
        def body(x):
            return x

        def f(mesh, x):
            # mxlint: disable=replication-soundness (host dedups later)
            g = shard_map(body, mesh, in_specs=(P("dp"),),
                          out_specs=P())
            return g(x)
    """, select=["replication-soundness"])
    assert issues == []


# ===================================================== pass 19: donation
def test_out_of_range_donation_fires():
    issues = run("""
        def body(x):
            return x

        def f():
            step = jax.jit(body, donate_argnums=(1,))
            return step
    """, select=["donation-soundness"])
    assert ids(issues) == ["donation-soundness"]
    assert "only 1 positional" in issues[0].message


def test_unknown_donate_argname_fires():
    issues = run("""
        def body(x):
            return x

        def f():
            step = jax.jit(body, donate_argnames=("params",))
            return step
    """, select=["donation-soundness"])
    assert ids(issues) == ["donation-soundness"]
    assert "'params'" in issues[0].message


def test_dropped_donation_provable_shape_mismatch_fires():
    issues = run("""
        def body(x):
            B, D = x.shape
            return jnp.zeros((B,))

        def f():
            step = jax.jit(body, donate_argnums=(0,))
            return step
    """, select=["donation-soundness"])
    assert ids(issues) == ["donation-soundness"]
    assert "silently dropped" in issues[0].message


def test_matching_output_keeps_donation_quiet():
    issues = run("""
        def body(x):
            B, D = x.shape
            return x * 2.0, jnp.zeros((B,))

        def f():
            step = jax.jit(body, donate_argnums=(0,))
            return step
    """, select=["donation-soundness"])
    assert issues == []


def test_unknown_output_shape_stays_quiet():
    # an opaque output could alias anything — no provable mismatch
    issues = run("""
        def helper(x):
            return x

        def body(x, f):
            B, D = x.shape
            return f(x)

        def g():
            step = jax.jit(body, donate_argnums=(0,))
            return step
    """, select=["donation-soundness"])
    assert issues == []


def test_use_after_donate_fires():
    issues = run("""
        def body(x):
            return x * 2.0

        def f(x):
            step = jax.jit(body, donate_argnums=(0,))
            y = step(x)
            z = x + 1.0
            return y, z
    """, select=["donation-soundness"])
    assert ids(issues) == ["donation-soundness"]
    assert "deleted or donated" in issues[0].message


def test_rebind_washes_use_after_donate():
    issues = run("""
        def body(x):
            return x * 2.0

        def f(x):
            step = jax.jit(body, donate_argnums=(0,))
            x = step(x)
            z = x + 1.0
            return z
    """, select=["donation-soundness"])
    assert issues == []


def test_self_attribute_use_after_donate_fires():
    issues = run("""
        class T:
            def go(self):
                step = jax.jit(lambda p: p, donate_argnums=(0,))
                out = step(self.params)
                norm = jnp.sum(self.params["w"])
                return out, norm
    """, select=["donation-soundness"])
    assert ids(issues) == ["donation-soundness"]
    assert "'self.params'" in issues[0].message


def test_decorator_donation_checked():
    issues = run("""
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def body(x):
            B, D = x.shape
            return jnp.zeros((B,))
    """, select=["donation-soundness"])
    assert ids(issues) == ["donation-soundness"]


def test_donation_suppression_is_honored():
    issues = run("""
        def body(x):
            return x * 2.0

        def f(x):
            step = jax.jit(body, donate_argnums=(0,))
            y = step(x)
            # mxlint: disable=donation-soundness (x is a host copy)
            z = x + 1.0
            return y, z
    """, select=["donation-soundness"])
    assert issues == []


# ================================================== the real tree gates
def test_repo_tree_is_clean_under_spmd_passes():
    """ISSUE-19 acceptance: the swept tree carries no SPMD findings."""
    issues = lint_paths([os.path.join(REPO, "mxnet_tpu"),
                         os.path.join(REPO, "tools")],
                        select=SPMD_PASSES)
    assert issues == [], "\n".join(str(i) for i in issues)


# =============================================== satellite: cache key glob
def test_new_pass_source_busts_cache_key(tmp_path):
    """Adding or editing ANY file under tools/mxlint/ must miss the
    warm cache — the side-input hash globs the package instead of a
    hard-coded module list."""
    root = tmp_path
    (root / "tools" / "mxlint" / "passes").mkdir(parents=True)
    target = root / "x.py"
    target.write_text("x = 1\n")
    k1 = cache_key([str(target)], None, None, root=str(root))
    newpass = root / "tools" / "mxlint" / "passes" / "shiny.py"
    newpass.write_text("# a new pass module\n")
    k2 = cache_key([str(target)], None, None, root=str(root))
    assert k1 != k2, "adding a pass module must change the key"
    newpass.write_text("# the pass module, edited\n")
    k3 = cache_key([str(target)], None, None, root=str(root))
    assert k3 != k2, "editing a pass module must change the key"


# ============================================ satellite: --profile-passes
def test_profile_passes_prints_timing_table(tmp_path):
    f = tmp_path / "m.py"
    f.write_text("def g(x):\n    return x\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.mxlint", "--no-cache",
         "--profile-passes", "--select", "donation-soundness", str(f)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "pass timings" in proc.stderr
    assert "donation-soundness" in proc.stderr
    assert "(parse+harvest)" in proc.stderr
