"""End-to-end system tests: the full HWTool flow on the paper's pipelines,
validated against the paper's own published numbers (fig. 9)."""
import numpy as np
import pytest
from fractions import Fraction

from repro.apps import Convolution, Stereo, golden_convolution
from repro.core import CompileOptions, compile_pipeline


# paper fig. 9: CONVOLUTION at each throughput -> (T_eff, cycles)
PAPER_CONV = {
    Fraction(1, 8): (0.12, 16_851_000),
    Fraction(1, 4): (0.25, 8_425_000),
    Fraction(1, 2): (0.49, 4_213_000),
    Fraction(1): (0.98, 2_106_000),
    Fraction(2): (1.97, 1_053_000),
    Fraction(4): (3.94, 527_000),
    Fraction(8): (7.87, 263_000),
}


@pytest.mark.parametrize("T", sorted(PAPER_CONV))
def test_convolution_matches_paper_fig9(T, conv_design_t1):
    d = conv_design_t1 if T == Fraction(1) else compile_pipeline(
        Convolution(), T=T)
    t_eff, cycles = PAPER_CONV[T]
    # throughput normalization reproduces the paper's T column (which is
    # rounded to 2-3 significant digits; 7.8755 vs printed 7.87)
    assert abs(float(d.T) - t_eff) < 0.01, (float(d.T), t_eff)
    # cycle counts within ~1% (ours include pipeline-fill latency)
    assert abs(d.cycles_per_frame() - cycles) / cycles < 0.011
    assert d.check_schedule()


def test_conv_resource_scaling_near_linear(conv_design_t1):
    """Paper fig. 10: compute resources scale ~linearly with T."""
    clbs_1 = conv_design_t1.resources.clbs
    clbs_4 = compile_pipeline(Convolution(), T=Fraction(4)).resources.clbs
    ratio = clbs_4 / clbs_1
    assert 3.0 < ratio < 5.0, ratio


def test_auto_fifo_overhead_vs_manual(conv_design_t1):
    """Paper §7.3 / fig. 11: automatic FIFO allocation costs BRAM vs the
    manual allocation (DMA absorbs pad/crop bursts); compute cost is the
    same."""
    auto = conv_design_t1
    manual = compile_pipeline(
        Convolution(), T=Fraction(1),
        options=CompileOptions(manual_fifo_overrides={"crop": 0, "pad": 0}))
    assert auto.resources.brams > manual.resources.brams
    assert auto.resources.brams <= 4 * manual.resources.brams
    assert abs(auto.resources.clbs - manual.resources.clbs) < 32


def test_compiled_design_runs_bit_exact():
    conv = Convolution(w=64, h=32)
    d = compile_pipeline(conv, T=Fraction(1))
    rng = np.random.RandomState(0)
    img = rng.randint(0, 256, (32, 64)).astype(np.int64)
    out = d.run({"convolution.in": img})
    assert np.array_equal(out, golden_convolution(img, conv.kernel))


def test_stereo_static_interface():
    """Stereo has no bursty ops -> the interface solve keeps it Static."""
    d = compile_pipeline(Stereo(w=64, h=16, nd=8), T=Fraction(1, 2))
    assert d.kind == "Static"
    assert d.check_schedule()


def test_solver_modes_agree(conv_design_t1):
    """Z3 and LP both solve register minimization exactly -> equal totals.
    (conv_design_t1 compiled with the default "z3" solver, which falls
    back to the exact LP when z3-solver is not installed.)"""
    b = compile_pipeline(Convolution(), T=Fraction(1),
                         options=CompileOptions(fifo_solver="lp"))
    assert conv_design_t1.fifo.total_bits == b.fifo.total_bits


# ---- typed options API (CompileOptions / SimOptions) ----

def test_compile_options_deprecated_kwargs_equivalent():
    """Loose compile_pipeline kwargs still work behind a
    DeprecationWarning and produce the same design as CompileOptions;
    mixing both is a TypeError; typos fail fast on the dataclass."""
    with pytest.warns(DeprecationWarning, match="compile_pipeline"):
        old = compile_pipeline(Convolution(), T=Fraction(1),
                               fifo_solver="lp")
    new = compile_pipeline(Convolution(), T=Fraction(1),
                           options=CompileOptions(fifo_solver="lp"))
    assert old.fifo.total_bits == new.fifo.total_bits
    assert old.report() == new.report()
    with pytest.raises(TypeError, match="both"):
        compile_pipeline(Convolution(),
                         options=CompileOptions(fifo_solver="lp"),
                         fifo_solver="lp")
    with pytest.raises(TypeError):
        CompileOptions(fifo_slover="lp")      # typo: typed options catch it


def test_sim_options_deprecated_kwargs_equivalent(conv_design_t1):
    from repro.core import SimOptions
    with pytest.warns(DeprecationWarning, match="HWDesign.simulate"):
        old = conv_design_t1.simulate(frames=2, engine="vector")
    new = conv_design_t1.simulate(options=SimOptions(frames=2,
                                                     engine="vector"))
    assert (old.cycles, old.sink_tokens) == (new.cycles, new.sink_tokens)
    assert old.hwm_by_key() == new.hwm_by_key()
    with pytest.raises(TypeError, match="both"):
        conv_design_t1.simulate(options=SimOptions(frames=2), frames=2)


def test_optimize_fifos_options(conv_design_t1):
    from repro.core import SimOptions
    with pytest.warns(DeprecationWarning, match="optimize_fifos"):
        old = conv_design_t1.optimize_fifos(frames=2)
    new = conv_design_t1.optimize_fifos(options=SimOptions(frames=2))
    assert old == new


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_rule(from_env, tmp_path):
    """The persistent compilation cache lives in $JAX_COMPILATION_CACHE_DIR
    where that is set, else at the fixed <checkout>/.cache/jax, and a
    compile writes its entry there (a fresh process: JAX reads the
    variable when it is imported)."""
    import os
    import subprocess
    import sys
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_COMPILATION_CACHE",
                                "JAX_PERSISTENT_CACHE"))}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    want = str(tmp_path) if from_env \
        else os.path.join(checkout, ".cache", "jax")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = ("import os, jax, jax.numpy as jnp\n"
            "from repro.cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == path\n"
            "jax.jit(lambda x: x * 7 - 3)(jnp.arange(11)).block_until_ready()\n"
            "print(path)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=checkout)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-1] == want
    assert os.listdir(want)
