"""Engine construction behind one switch: ``make_engine(backend=...)``.

The serving runtime is backend-agnostic — ``Cluster`` and every policy
drive whatever implements the engine surface — so the choice between the
real jit'd ``Engine`` and the analytic-time ``SimEngine`` is a
construction-time flag, threaded through ``launch/serve.py --backend`` and
the benchmarks. Imports are lazy per backend: asking for ``"sim"`` never
pays the jax import.
"""
from __future__ import annotations

BACKENDS = ("real", "sim")


def make_engine(backend: str, engine_id: int, cfg, params=None, **kw):
    """Build one engine of the requested backend.

    ``"real"`` needs ``params`` (jit'd forwards); ``"sim"`` ignores them
    and additionally accepts ``calibration=`` (a
    ``simengine.SimCalibration``). All other keywords — ``slots``,
    ``capacity``, ``chunk_size``, ``chip``, ``speed_factor`` — are shared.
    """
    if backend == "sim":
        from repro.serving.simengine import SimEngine
        for k in ("paged", "pool_blocks"):   # real-only KV-layout knobs
            kw.pop(k, None)                  # (block_size is shared)
        return SimEngine(engine_id, cfg, params, **kw)
    if backend == "real":
        from repro.serving.engine import Engine
        if params is None:
            raise ValueError("backend='real' requires model params "
                             "(backend='sim' runs without them)")
        kw.pop("calibration", None)     # sim-only knob
        return Engine(engine_id, cfg, params, **kw)
    raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")


def init_real_params(cfg, seed: int = 0):
    """Params for the real backend, with jax imported here — not at the
    caller's module load — so sim-only invocations never pay for it. The
    one param-init recipe every launcher and calibration path shares.
    Jitted, so each weight is drawn and cast in one program: no f32 copy
    of a stacked weight is ever held on the device."""
    from functools import partial

    import jax
    from repro.models import transformer as T
    return jax.jit(partial(T.init_params, cfg))(jax.random.PRNGKey(seed))


def local_chip():
    """The ``core.hardware`` chip this process's jax drives, or None on
    the CPU. An accelerator whose ``device_kind`` has no entry in
    ``hardware.DEVICE_KINDS`` raises."""
    import jax
    from repro.core.hardware import chip_for_device_kind
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return chip_for_device_kind(dev.device_kind)
