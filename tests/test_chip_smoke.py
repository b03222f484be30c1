"""chip_smoke.py rehearsed on the CPU: its phases at toy width (the test
steers the size — the script has no option for it), the four-chip phase on
four virtual devices, and the contract that without a chip, or with any
phase failing, no result line is printed."""

import importlib.util
import os

import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

SEQ, BATCH = 128, 2


def toy_model(tensor_parallel: bool = False):
    paddle.seed(chip_smoke.SEED)
    return GPT(GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                         num_heads=4, max_seq_len=SEQ,
                         tensor_parallel=tensor_parallel))


def _kernels():
    chip_smoke.phase_kernels(2, 16, SEQ, 1, interpret=True)


def _train():
    chip_smoke.phase_train(toy_model(), BATCH, SEQ, kernels_required=False)


def _serve():
    # the ragged kernel in interpret mode: the "never the reference"
    # check is live on the CPU too
    chip_smoke.phase_serve(toy_model(), n_requests=3, prompt_range=(9, 16),
                           max_tokens=4, num_blocks=64, attn_impl="ragged")


def _sharded():
    chip_smoke.phase_sharded(toy_model, 4, SEQ)


def _sharded_serve():
    chip_smoke.phase_sharded_serve(toy_model(), n_requests=3,
                                   prompt_range=(9, 16), max_tokens=4,
                                   num_blocks=64, attn_impl="ragged")


@pytest.fixture(autouse=True, scope="module")
def _serve_each_engine_once():
    """Several tests serve the same toy model, prompts and options (the
    serve phase, the four-device serve and the two near-tie cases): run
    each distinct engine once per module and hand later callers its
    result."""
    real, done = chip_smoke._serve, {}

    def serve(phase, model, prompts, max_tokens, num_blocks, **kw):
        key = (repr(prompts), max_tokens, num_blocks,
               repr(sorted(kw.items())))
        if key not in done:
            done[key] = real(phase, model, prompts, max_tokens, num_blocks,
                             **kw)
        eng, streams = done[key]
        return eng, [list(t) for t in streams]

    chip_smoke._serve = serve
    yield
    chip_smoke._serve = real


PHASES = {"kernels": _kernels, "train": _train, "serve": _serve,
          "sharded_on_four_virtual_devices": _sharded,
          "sharded_serve_on_four_virtual_devices": _sharded_serve}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_passes_at_toy_width(phase, capsys):
    PHASES[phase]()
    assert f"[{phase.split('_')[0]}" in capsys.readouterr().out


def test_main_fails_on_cpu_and_prints_no_result(capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="platform"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_four_chip_option_needs_four_chips(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "phase_device",
                        lambda platform, count: chip_smoke.check(
                            count == 1, "need 4 device(s)"))
    with pytest.raises(chip_smoke.SmokeFailure, match="4 device"):
        chip_smoke.main(["--chips", "4"])
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("failing", ["kernels", "train", "serve"])
def test_a_failed_phase_stops_the_run(failing, monkeypatch, capsys):
    """main() with the device phase let through and one phase failing: the
    failure leaves main, later phases do not run, no result line."""
    ran = []

    def stub(name):
        def phase(*a, **kw):
            ran.append(name)
            chip_smoke.check(name != failing, f"{name} failed")
        return phase

    monkeypatch.setattr(chip_smoke, "phase_device", lambda *a: {
        "platform": "tpu", "kind": "stub", "count": 1})
    for name in ("kernels", "train", "serve"):
        monkeypatch.setattr(chip_smoke, f"phase_{name}", stub(name))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent")
    with pytest.raises(chip_smoke.SmokeFailure, match=f"{failing} failed"):
        chip_smoke.main([])
    assert ran[-1] == failing
    assert '"ok"' not in capsys.readouterr().out


def test_train_phase_checks_bite(monkeypatch):
    """A check inside a real phase fails the phase: with a zero band around
    ln(vocab) the toy model's first loss is out of it."""
    monkeypatch.setattr(chip_smoke, "FIRST_LOSS_TOL", 0.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="first loss"):
        _train()


def test_serve_phase_rejects_the_reference_path():
    with pytest.raises(chip_smoke.SmokeFailure, match="gave way"):
        chip_smoke.phase_serve(toy_model(), n_requests=1,
                               prompt_range=(8, 8), max_tokens=2,
                               num_blocks=16, attn_impl="reference")


@pytest.mark.parametrize("tol,passes", [(float("inf"), True), (0.0, False)])
def test_sharded_serve_judges_a_parting_by_the_logit_gap(
        tol, passes, monkeypatch):
    """Where the tensor-parallel stream leaves the one-device stream, the
    one-device logits of the two tokens decide: inside the tolerance it is
    a near-tie, outside it the phase fails."""
    served = chip_smoke._serve

    def serve(phase, *a, **kw):
        eng, streams = served(phase, *a, **kw)
        streams = [list(t) for t in streams]
        if phase.endswith("tp4"):
            streams[0][2] = (streams[0][2] + 1) % 256
        return eng, streams

    monkeypatch.setattr(chip_smoke, "_serve", serve)
    monkeypatch.setattr(chip_smoke, "NEAR_TIE_TOL", tol)
    if passes:
        _sharded_serve()
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="logit gap"):
            _sharded_serve()


@pytest.mark.parametrize("env_dir", ["/somewhere/outside", None])
def test_compile_cache_is_placed_from_outside(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: code sets nothing (JAX reads the
    variable itself). Unset: `<checkout>/.jax_cache`, never a temporary
    name — the path is part of the cache key."""
    import jax

    from paddle_tpu.utils.compile_cache import place_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert place_compile_cache() == want
        assert place_compile_cache() == want          # the same, always
        assert updates == [("jax_compilation_cache_dir", want)] * 2
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert place_compile_cache() == env_dir
        assert updates == []
