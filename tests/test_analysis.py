"""cooptlint self-tests: one good + one bad fixture per finding code,
baseline round-trip, inline suppression, and the repo-gate invariant that
`python -m repro.analysis src/repro` exits 0 on the committed tree."""
import json
import os
import subprocess
import sys
import textwrap

from repro.analysis import run_suite, write_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(tmp_path, relpath, source, **kw):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    live, suppressed, baselined, report = run_suite(
        [str(tmp_path)], root=str(tmp_path), **kw)
    return live, suppressed, baselined, report


def _codes(findings):
    return [f.code for f in findings]


# ------------------------------------------------------------- COOPT001 --
BAD_SYNC = """
    import numpy as np

    class Engine:
        def _build_step(self, toks):
            return np.asarray(toks)     # stray sync on the plan path
"""

GOOD_SYNC = """
    import numpy as np

    class Engine:
        def _execute(self, sb):
            return np.asarray(sb.toks)  # the designated host boundary
"""


def test_host_sync_bad(tmp_path):
    live, *_ = _lint(tmp_path, "serving/engine.py", BAD_SYNC)
    assert _codes(live) == ["COOPT001"]
    assert live[0].symbol == "Engine._build_step"


def test_host_sync_good(tmp_path):
    live, *_ = _lint(tmp_path, "serving/engine.py", GOOD_SYNC)
    assert live == []


def test_host_sync_only_serving_modules(tmp_path):
    # the same sync outside serving/ is not this pass's business
    live, *_ = _lint(tmp_path, "models/util.py", BAD_SYNC)
    assert live == []


# ------------------------------------------------------------- COOPT002 --
BAD_DONATE = """
    import jax

    class Engine:
        def __init__(self):
            self._step_fn = jax.jit(self._impl, donate_argnums=(1,))

        def step(self, params, cache):
            logits, new_cache = self._step_fn(params, cache)
            return logits, cache.shape   # read after donation
"""

GOOD_DONATE = """
    import jax

    class Engine:
        def __init__(self):
            self._step_fn = jax.jit(self._impl, donate_argnums=(1,))

        def step(self, params, cache):
            logits, cache = self._step_fn(params, cache)  # rebound
            return logits, cache.shape
"""


def test_donation_bad(tmp_path):
    live, *_ = _lint(tmp_path, "serving/x.py", BAD_DONATE)
    assert _codes(live) == ["COOPT002"]
    assert "cache" in live[0].message


def test_donation_good(tmp_path):
    live, *_ = _lint(tmp_path, "serving/x.py", GOOD_DONATE)
    assert live == []


def test_donation_dict_dispatch(tmp_path):
    # the Engine._execute idiom: fn looked up from a dict of donating jits
    src = """
    import jax

    class Engine:
        def __init__(self):
            self._a_fn = jax.jit(self._a, donate_argnums=(0,))
            self._b_fn = jax.jit(self._b, donate_argnums=(0,))

        def run(self, kind, cache):
            fn = {"a": self._a_fn, "b": self._b_fn}[kind]
            out = fn(cache)
            return out, cache.shape      # read after donation
    """
    live, *_ = _lint(tmp_path, "serving/x.py", src)
    assert _codes(live) == ["COOPT002"]


# ------------------------------------------------------------- COOPT003 --
BAD_MESH = """
    from repro.kernels import ops

    def trace_step(ctx, fn, x):
        ops.set_mesh_ctx(ctx)            # installed, never restored
        return fn(x)
"""

GOOD_MESH = """
    from repro.kernels import ops

    def trace_step(ctx, fn, x):
        saved = ops.mesh_ctx()
        ops.set_mesh_ctx(ctx)
        try:
            return fn(x)
        finally:
            ops.set_mesh_ctx(saved)
"""


def test_mesh_ctx_bad(tmp_path):
    live, *_ = _lint(tmp_path, "launch/x.py", BAD_MESH)
    assert _codes(live) == ["COOPT003"]


def test_mesh_ctx_good(tmp_path):
    live, *_ = _lint(tmp_path, "launch/x.py", GOOD_MESH)
    assert live == []


# ------------------------------------------------------------- COOPT004 --
BAD_TRACE = """
    import jax

    INTERPRET = True

    def configure():
        global INTERPRET
        INTERPRET = False

    @jax.jit
    def step(x):
        return run(x, interpret=INTERPRET)   # baked at trace time
"""

GOOD_TRACE = """
    import jax
    from functools import partial

    INTERPRET = True

    def configure():
        global INTERPRET
        INTERPRET = False

    @partial(jax.jit, static_argnames=("interpret",))
    def _step(x, *, interpret):
        return run(x, interpret=interpret)

    def step(x):
        return _step(x, interpret=INTERPRET)   # read OUTSIDE the jit
"""


def test_trace_safety_global_bad(tmp_path):
    live, *_ = _lint(tmp_path, "kernels_misc/x.py", BAD_TRACE)
    assert _codes(live) == ["COOPT004"]
    assert "INTERPRET" in live[0].message


def test_trace_safety_global_good(tmp_path):
    live, *_ = _lint(tmp_path, "kernels_misc/x.py", GOOD_TRACE)
    assert live == []


def test_trace_safety_mutable_self_attr(tmp_path):
    src = """
    import jax

    class Engine:
        def __init__(self):
            self.cache = None
            self._fn = jax.jit(self._impl)

        def place(self, c):
            self.cache = c               # mutated outside __init__

        def _impl(self, x):
            return x + self.cache        # closure over per-step state
    """
    live, *_ = _lint(tmp_path, "serving/x.py", src)
    assert _codes(live) == ["COOPT004"]
    assert "self.cache" in live[0].message


def test_trace_safety_full_pool_gather(tmp_path):
    src = """
    import jax.numpy as jnp

    def decode(pool, pt):
        return jnp.take(pool, pt, axis=0)
    """
    live, *_ = _lint(tmp_path / "hot", "kernels/hot.py", src)
    assert _codes(live) == ["COOPT004"]
    # ref.py is the designated naive-formulation oracle
    live, *_ = _lint(tmp_path / "ref", "kernels/ref.py", src)
    assert live == []


# ------------------------------------------------------------- COOPT005 --
_KERNEL_TMPL = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(q, pages, phys, *, interpret):
        def page_idx(b, s, phys):
            return ({DEREF}, 0, 0)
        return pl.pallas_call(
            _kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4, 8),
                in_specs=[
                    pl.BlockSpec((1, 1, {BQ}, 128),
                                 lambda b, s, phys: (b, s, 0, 0)),
                    pl.BlockSpec((1, {BQ}, 128), page_idx),
                ],
                out_specs=[pl.BlockSpec((1, 1, {BQ}, 128),
                                        lambda b, s, phys: (b, s, 0, 0))],
                scratch_shapes=[pltpu.VMEM(({BQ}, 128), jnp.float32)],
            ),
            interpret=interpret,
        )(phys, q, pages)
"""


def _kernel_src(deref="jnp.maximum(phys[b, s], 0)", bq=64):
    return _KERNEL_TMPL.replace("{DEREF}", deref).replace("{BQ}", str(bq))


def test_pallas_sentinel_clamped_ok(tmp_path):
    live, _s, _b, report = _lint(tmp_path, "kernels/k.py", _kernel_src())
    assert live == []
    assert len(report) == 1 and report[0]["under_budget"]


def test_pallas_sentinel_unclamped_flagged(tmp_path):
    live, *_ = _lint(tmp_path, "kernels/k.py",
                     _kernel_src(deref="phys[b, s]"))
    assert _codes(live) == ["COOPT005"]
    assert "sentinel" in live[0].message


def test_pallas_grid_index_deref_flagged(tmp_path):
    # subscripting a grid index (not a prefetch ref) inside the index_map
    live, *_ = _lint(tmp_path, "kernels/k.py",
                     _kernel_src(deref="jnp.maximum(b[s], 0)"))
    assert _codes(live) == ["COOPT005"]
    assert "grid index" in live[0].message


def test_pallas_vmem_budget(tmp_path):
    # same kernel, huge query block: must blow a 1 MiB budget
    live, _s, _b, report = _lint(tmp_path, "kernels/k.py",
                                 _kernel_src(bq=4096),
                                 vmem_budget=1 << 20)
    assert _codes(live) == ["COOPT005"]
    assert "budget" in live[0].message
    assert not report[0]["under_budget"]
    assert report[0]["est_vmem_bytes"] > (1 << 20)


_LAYER_KERNEL = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(q, pool, phys, layer, *, interpret):
        def page_idx(b, s, phys, lyr):
            return (lyr[0], {DEREF}, 0, 0)
        return pl.pallas_call(
            _kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(4, 8),
                in_specs=[
                    pl.BlockSpec((1, 1, 64, 128),
                                 lambda b, s, phys, lyr: (b, s, 0, 0)),
                    pl.BlockSpec((None, 1, {BQ}, 128), page_idx),
                ],
                out_specs=[pl.BlockSpec((1, 1, 64, 128),
                                        lambda b, s, phys, lyr: (b, s, 0, 0))],
            ),
            interpret=interpret,
        )(phys, layer, q, pool)
"""


def _layer_kernel_src(deref="jnp.maximum(phys[b, s], 0)", bq=64):
    return _LAYER_KERNEL.replace("{DEREF}", deref).replace("{BQ}", str(bq))


def test_pallas_layer_scalar_and_squeezed_dims_ok(tmp_path):
    """A second scalar-prefetch ref read at a literal index (the pool's
    layer) is a scalar argument, not a page table; a ``None`` (squeezed)
    block dim holds one element, not an unresolved 128."""
    live, _s, _b, report = _lint(tmp_path, "kernels/k.py",
                                 _layer_kernel_src())
    assert live == []
    (entry,) = report
    assert entry["unresolved_dims"] == []
    assert entry["block_bytes"] == 3 * 64 * 128 * 4


def test_pallas_layer_kernel_table_still_checked(tmp_path):
    # the page table beside the layer scalar keeps its sentinel rule
    live, *_ = _lint(tmp_path, "kernels/k.py",
                     _layer_kernel_src(deref="phys[b, s]"))
    assert _codes(live) == ["COOPT005"]
    assert "'phys'" in live[0].message


def test_vmem_report_covers_repo_kernels():
    """The four pooled serving kernels must appear in the repo's VMEM
    report and sit under the default budget."""
    live, _s, _b, report = run_suite(
        [os.path.join(REPO_ROOT, "src", "repro", "kernels")],
        root=REPO_ROOT, select=["COOPT005"])
    names = {e["kernel"] for e in report}
    for k in ("paged_pool_decode", "flash_chunk_prefill",
              "paged_latent_decode", "latent_chunk_prefill"):
        assert k in names, f"{k} missing from VMEM report"
    assert all(e["under_budget"] for e in report)


# ------------------------------------------------------------- COOPT006 --
BAD_EXCEPT = """
    class Worker:
        def run(self):
            while True:
                try:
                    self.step()
                except Exception:
                    pass                    # fault swallowed
"""

GOOD_EXCEPT = """
    class Worker:
        def run(self):
            try:
                self.step()
            except Exception as exc:
                self.post(exc)              # recorded, not swallowed
            try:
                self.step()
            except ValueError:
                pass                        # narrow handlers are policy
            try:
                self.step()
            except Exception:
                self.note()
                raise                       # re-raised
"""


def test_exceptions_bad(tmp_path):
    live, *_ = _lint(tmp_path, "serving/worker.py", BAD_EXCEPT)
    assert _codes(live) == ["COOPT006"]
    assert live[0].symbol == "Worker.run"


def test_exceptions_good(tmp_path):
    live, *_ = _lint(tmp_path, "serving/worker.py", GOOD_EXCEPT)
    assert live == []


def test_exceptions_bound_but_unused(tmp_path):
    src = BAD_EXCEPT.replace("except Exception:",
                             "except Exception as exc:")
    live, *_ = _lint(tmp_path, "serving/worker.py", src)
    assert _codes(live) == ["COOPT006"]


def test_exceptions_bare_except(tmp_path):
    src = BAD_EXCEPT.replace("except Exception:", "except:")
    live, *_ = _lint(tmp_path, "serving/worker.py", src)
    assert _codes(live) == ["COOPT006"]
    assert "bare except" in live[0].message


def test_exceptions_only_serving_modules(tmp_path):
    # the same swallow outside serving/ is not this pass's business
    live, *_ = _lint(tmp_path, "benchmarks/run.py", BAD_EXCEPT)
    assert live == []


# --------------------------------------------- suppression and baseline --
def test_inline_suppression(tmp_path):
    src = BAD_SYNC.replace(
        "return np.asarray(toks)     # stray sync on the plan path",
        "return np.asarray(toks)  # coopt: allow[COOPT001]")
    live, suppressed, *_ = _lint(tmp_path, "serving/engine.py", src)
    assert live == [] and _codes(suppressed) == ["COOPT001"]


def test_inline_suppression_line_above(tmp_path):
    src = BAD_SYNC.replace(
        "return np.asarray(toks)     # stray sync on the plan path",
        "# coopt: allow[COOPT001]\n            return np.asarray(toks)")
    live, suppressed, *_ = _lint(tmp_path, "serving/engine.py", src)
    assert live == [] and _codes(suppressed) == ["COOPT001"]


def test_inline_suppression_wrong_code_does_not_apply(tmp_path):
    src = BAD_SYNC.replace(
        "return np.asarray(toks)     # stray sync on the plan path",
        "return np.asarray(toks)  # coopt: allow[COOPT005]")
    live, suppressed, *_ = _lint(tmp_path, "serving/engine.py", src)
    assert _codes(live) == ["COOPT001"] and suppressed == []


def test_baseline_round_trip(tmp_path):
    p = tmp_path / "serving" / "engine.py"
    p.parent.mkdir(parents=True)
    p.write_text(textwrap.dedent(BAD_SYNC))
    live, _s, baselined, _r = run_suite([str(tmp_path)], root=str(tmp_path))
    assert _codes(live) == ["COOPT001"]

    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), live)
    data = json.loads(bl.read_text())
    assert len(data["findings"]) == 1
    assert "justification" in data["findings"][0]

    live2, _s, baselined2, _r = run_suite(
        [str(tmp_path)], root=str(tmp_path), baseline_path=str(bl))
    assert live2 == [] and _codes(baselined2) == ["COOPT001"]

    # baseline matching ignores line drift: shift the file down two lines
    p.write_text("# pad\n# pad\n" + textwrap.dedent(BAD_SYNC))
    live3, _s, baselined3, _r = run_suite(
        [str(tmp_path)], root=str(tmp_path), baseline_path=str(bl))
    assert live3 == [] and _codes(baselined3) == ["COOPT001"]


# ----------------------------------------------------------- repo gate --
def test_repo_is_clean():
    """The committed tree must pass its own linter — the CI gate."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src")
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src/repro",
         "--format", "json"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True)
    payload = json.loads(res.stdout)
    assert res.returncode == 0, payload["findings"]
    assert payload["findings"] == []
    assert len(payload["vmem_report"]) >= 4
