"""Functional diffusion schedulers: DDIM, Euler (discrete), DPM-Solver++ (2M).

The reference delegates scheduling to diffusers and runs it replicated on
every rank (SURVEY.md §1: "the denoising loop, schedulers ... are NOT
reimplemented"); its CLI exposes exactly these three
(/root/reference/scripts/run_sdxl.py:33-36 `--scheduler {ddim,euler,
dpm-solver}`).  A TPU build needs them *functional* so the whole denoise loop
can live inside one `lax.scan` under a single jit: every per-step coefficient
is precomputed into fixed tables at `set_timesteps` time, and `step()` is a
pure function of (sample, model_output, step_index, carry-state) — no data-
dependent Python, no dynamic shapes.

Numerics follow diffusers==0.24.0 (the reference's pin) with the SD/SDXL
defaults: scaled_linear betas in [0.00085, 0.012], 1000 train steps, epsilon
prediction, "leading" timestep spacing, steps_offset=1.

Multistep history (DPM-Solver 2M) is explicit carry state (`init_state`),
exactly like the displaced-patch activation state — it threads through the
scan.

``step_index`` may be a scalar (the scan/stepwise path) or a ``[B]``
vector (the packed cohort step, serve/executors.py `step_run`): every
table lookup broadcasts per batch row through `_per_row`, which is a
no-op on scalars — the scalar path traces the exact program it always
did, and the vector path applies row ``j``'s coefficients to row ``j``
only (elementwise, so bitwise identical per row to the scalar run).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp
import numpy as np


def _per_row(coef, ref):
    """Shape a per-row coefficient against a batch-major sample: a
    scalar passes through untouched (the scalar path's program is
    byte-for-byte what it was); a ``[B]`` vector reshapes to
    ``[B, 1, ..., 1]`` so it broadcasts along ``ref``'s batch axis."""
    coef = jnp.asarray(coef)
    if coef.ndim == 0:
        return coef
    return coef.reshape(coef.shape + (1,) * (jnp.ndim(ref) - 1))


def _make_alphas_cumprod(
    num_train_timesteps: int, beta_start: float, beta_end: float, beta_schedule: str
) -> np.ndarray:
    if beta_schedule == "scaled_linear":
        betas = (
            np.linspace(beta_start**0.5, beta_end**0.5, num_train_timesteps) ** 2
        )
    elif beta_schedule == "linear":
        betas = np.linspace(beta_start, beta_end, num_train_timesteps)
    else:
        raise ValueError(f"unsupported beta_schedule {beta_schedule!r}")
    return np.cumprod(1.0 - betas, axis=0)


def _table(values) -> jnp.ndarray:
    """A float32 schedule table on the device, cast on the host:
    ``jnp.asarray(float64 values, jnp.float32)`` dispatches a convert program,
    and `set_timesteps` runs on every request (twice: the pipeline's and the
    runner's re-pin)."""
    return jnp.asarray(np.asarray(values, np.float32))


def _leading_timesteps(num_train_timesteps: int, n: int, steps_offset: int) -> np.ndarray:
    step_ratio = num_train_timesteps // n
    ts = (np.arange(n) * step_ratio).round()[::-1].astype(np.int64) + steps_offset
    return ts


@dataclasses.dataclass
class BaseScheduler:
    num_train_timesteps: int = 1000
    beta_start: float = 0.00085
    beta_end: float = 0.012
    beta_schedule: str = "scaled_linear"
    steps_offset: int = 1
    prediction_type: str = "epsilon"

    def __post_init__(self):
        if self.prediction_type not in ("epsilon", "v_prediction"):
            raise NotImplementedError(
                "prediction_type must be 'epsilon' or 'v_prediction'"
            )
        self._alphas_cumprod = _make_alphas_cumprod(
            self.num_train_timesteps, self.beta_start, self.beta_end, self.beta_schedule
        )
        self.num_inference_steps = None

    def _to_epsilon(self, sample, model_output, alpha_cumprod_t):
        """Convert the model output to an epsilon prediction.

        SD 2.x checkpoints are v-prediction (v = alpha*eps - sigma*x0), which
        the reference inherits from diffusers' scheduler configs; normalizing
        to epsilon keeps one update rule per sampler.
        """
        if self.prediction_type == "epsilon":
            return model_output
        a = jnp.sqrt(alpha_cumprod_t)
        s = jnp.sqrt(1.0 - alpha_cumprod_t)
        return a * model_output + s * sample.astype(jnp.float32)

    # ---- shared API -------------------------------------------------------
    @property
    def init_noise_sigma(self) -> float:
        return 1.0

    def scale_model_input(self, sample, step_index):
        return sample

    def init_state(self, latent_shape, dtype=jnp.float32) -> Dict[str, Any]:
        """Carry state threaded through the scan (empty for single-step methods)."""
        return {}

    def timesteps(self) -> jnp.ndarray:
        assert self.num_inference_steps is not None, "call set_timesteps first"
        return self._timesteps

    def add_noise(self, original, noise, step_index):
        """Noise a clean latent to the schedule point ``step_index`` — the
        img2img entry (diffusers add_noise parity): x_t = sqrt(ac_t) x0 +
        sqrt(1 - ac_t) eps at t = timesteps()[step_index]."""
        t = self.timesteps()[step_index]
        ac = _per_row(jnp.asarray(self._alphas_cumprod, jnp.float32)[t],
                      original)
        x0 = original.astype(jnp.float32)
        out = jnp.sqrt(ac) * x0 + jnp.sqrt(1.0 - ac) * noise.astype(jnp.float32)
        return out.astype(original.dtype)

    def step(self, sample, model_output, step_index, state):
        raise NotImplementedError


class DDIMScheduler(BaseScheduler):
    """Deterministic DDIM (eta=0), diffusers DDIMScheduler parity
    (set_alpha_to_one=False for SD/SDXL)."""

    def set_timesteps(self, n: int):
        self.num_inference_steps = n
        ts = _leading_timesteps(self.num_train_timesteps, n, self.steps_offset)
        prev_ts = ts - self.num_train_timesteps // n
        ac = self._alphas_cumprod
        final_alpha = ac[0]  # set_alpha_to_one=False
        alpha_t = ac[ts]
        alpha_prev = np.where(prev_ts >= 0, ac[np.clip(prev_ts, 0, None)], final_alpha)
        self._timesteps = jnp.asarray(ts)
        self._alpha_t = _table(alpha_t)
        self._alpha_prev = _table(alpha_prev)
        return self

    def step(self, sample, model_output, step_index, state):
        a_t = _per_row(self._alpha_t[step_index], sample)
        a_prev = _per_row(self._alpha_prev[step_index], sample)
        x = sample.astype(jnp.float32)
        eps = self._to_epsilon(sample, model_output.astype(jnp.float32), a_t)
        x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
        x_prev = jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps
        return x_prev.astype(sample.dtype), state


class EulerDiscreteScheduler(BaseScheduler):
    """diffusers EulerDiscreteScheduler parity (no churn/noise: s_churn=0)."""

    def set_timesteps(self, n: int):
        self.num_inference_steps = n
        ts = _leading_timesteps(self.num_train_timesteps, n, self.steps_offset)
        ac = self._alphas_cumprod
        sigmas_full = ((1.0 - ac) / ac) ** 0.5
        sigmas = sigmas_full[ts]
        self._timesteps = jnp.asarray(ts)
        self._sigmas = _table(np.append(sigmas, 0.0))
        self._init_noise_sigma = float((sigmas.max() ** 2 + 1) ** 0.5)
        return self

    @property
    def init_noise_sigma(self) -> float:
        return self._init_noise_sigma

    def scale_model_input(self, sample, step_index):
        sigma = _per_row(self._sigmas[step_index], sample)
        return (sample / jnp.sqrt(sigma**2 + 1.0)).astype(sample.dtype)

    def add_noise(self, original, noise, step_index):
        """Euler carries the sigma-space latent x = x0 + sigma * eps
        (diffusers EulerDiscreteScheduler.add_noise)."""
        sigma = _per_row(self._sigmas[step_index], original)
        out = original.astype(jnp.float32) + sigma * noise.astype(jnp.float32)
        return out.astype(original.dtype)

    def step(self, sample, model_output, step_index, state):
        # Euler works in the sigma-space parameterization x = x0 + sigma * n;
        # `sample` here is that scaled latent (init noise multiplied by
        # init_noise_sigma), `model_output` is epsilon (or v) at the descaled
        # input.
        sigma = _per_row(self._sigmas[step_index], sample)
        sigma_next = _per_row(self._sigmas[step_index + 1], sample)
        x = sample.astype(jnp.float32)
        ac_t = 1.0 / (sigma**2 + 1.0)  # alpha_cumprod of this sigma
        eps = self._to_epsilon(x * jnp.sqrt(ac_t), model_output.astype(jnp.float32), ac_t)
        # x0-from-epsilon in this parameterization: x0 = x - sigma * eps
        x_next = x + (sigma_next - sigma) * eps
        return x_next.astype(sample.dtype), state


class DPMSolverMultistepScheduler(BaseScheduler):
    """DPM-Solver++ 2M, diffusers algorithm_type='dpmsolver++' solver_order=2.

    Second-order multistep: carries the previous step's predicted x0 and
    lambda as explicit scan state.
    """

    solver_order: int = 2

    def set_timesteps(self, n: int):
        self.num_inference_steps = n
        ts = _leading_timesteps(self.num_train_timesteps, n, self.steps_offset)
        ac = self._alphas_cumprod
        alpha = np.sqrt(ac[ts])
        sigma = np.sqrt(1.0 - ac[ts])
        lam = np.log(alpha) - np.log(sigma)
        # final boundary: sigma->0, lambda->+inf; use the conventional
        # diffusers tail where the last step returns x0.
        self._timesteps = jnp.asarray(ts)
        self._alpha = _table(np.append(alpha, 1.0))
        self._sigma = _table(np.append(sigma, 0.0))
        self._lambda = _table(np.append(lam, np.inf))
        return self

    def init_state(self, latent_shape, dtype=jnp.float32):
        return {
            "x0_prev": jnp.zeros(latent_shape, jnp.float32),
            "lambda_prev": jnp.asarray(0.0, jnp.float32),
            "have_prev": jnp.asarray(False),
        }

    def step(self, sample, model_output, step_index, state):
        lam_t_raw = self._lambda[step_index]
        a_t = _per_row(self._alpha[step_index], sample)
        s_t = _per_row(self._sigma[step_index], sample)
        lam_t = _per_row(lam_t_raw, sample)
        a_n = _per_row(self._alpha[step_index + 1], sample)
        s_n = _per_row(self._sigma[step_index + 1], sample)
        lam_n = _per_row(self._lambda[step_index + 1], sample)

        x = sample.astype(jnp.float32)
        eps = self._to_epsilon(sample, model_output.astype(jnp.float32), a_t**2)
        x0 = (x - s_t * eps) / a_t

        h = lam_n - lam_t
        # 2M correction using the previous x0.  First step has no history and
        # the final step uses the first-order update (diffusers
        # lower_order_final=True: the 2M ratio h_prev/h degenerates as
        # sigma -> 0), both falling back to D = x0.
        h_prev = lam_t - _per_row(state["lambda_prev"], sample)
        r = h_prev / jnp.maximum(h, 1e-12)
        d_corr = (1.0 + 1.0 / (2.0 * jnp.maximum(r, 1e-12))) * x0 - (
            1.0 / (2.0 * jnp.maximum(r, 1e-12))
        ) * state["x0_prev"]
        use_corr = state["have_prev"] & (step_index < self.num_inference_steps - 1)
        d = jnp.where(_per_row(use_corr, x0), d_corr, x0)

        # dpmsolver++ update: x_next = (s_n/s_t) x - a_n (e^{-h} - 1) D;
        # at the final step sigma_next == 0 and h == inf, so this reduces to
        # x_next = a_n * D = x0 with no special-casing.
        ratio = jnp.where(s_t > 0, s_n / jnp.maximum(s_t, 1e-12), 0.0)
        em1 = jnp.expm1(-h)
        x_next = ratio * x - a_n * em1 * d

        # the carried scalars keep the shape they arrived with: scalar on
        # the scan/stepwise path, [B] on the packed cohort path
        new_state = {
            "x0_prev": x0,
            "lambda_prev": lam_t_raw,
            "have_prev": (jnp.asarray(True)
                          if jnp.ndim(state["have_prev"]) == 0
                          else jnp.ones_like(state["have_prev"])),
        }
        return x_next.astype(sample.dtype), new_state


@dataclasses.dataclass
class FlowMatchEulerScheduler(BaseScheduler):
    """Euler sampler for rectified-flow models (SD3-class MMDiT).

    Rectified flow parameterizes x_t = (1 - sigma) x0 + sigma * noise with
    sigma in [0, 1]; the model predicts the (straight-path) velocity
    v = noise - x0, and sampling integrates dx = v dsigma from 1 to 0.
    SD3 shifts the sigma grid toward the noisy end for high resolution:
    sigma' = shift * s / (1 + (shift - 1) * s) (Esser et al. 2024, eq. 23
    timestep shifting; shift=3 is the SD3-medium default).  The "timestep"
    fed to the model is sigma * num_train_timesteps.

    The reference pins diffusers 0.24, which predates flow matching
    entirely — this scheduler exists for the MMDiT family extension, not
    for reference parity.  Same functional contract as the others: fixed
    tables at set_timesteps, pure step(), empty carry state.
    """

    shift: float = 3.0

    def __post_init__(self):
        # no beta/alpha tables: flow sigmas are their own schedule.  The
        # inherited dataclass __init__ defaults prediction_type="epsilon";
        # a flow sampler has exactly one prediction convention, so pin it.
        self.prediction_type = "flow"
        self.num_inference_steps = None

    def set_timesteps(self, n: int):
        self.num_inference_steps = n
        lin = np.linspace(1.0, 1.0 / n, n)
        sig = self.shift * lin / (1.0 + (self.shift - 1.0) * lin)
        self._sigmas = _table(np.append(sig, 0.0))
        self._timesteps = _table(sig * self.num_train_timesteps)
        return self

    def add_noise(self, original, noise, step_index):
        """Flow interpolant x_t = (1 - sigma) x0 + sigma noise (the img2img
        entry; diffusers calls this scale_noise for flow-match schedulers)."""
        s = _per_row(self._sigmas[step_index], original)
        out = (1.0 - s) * original.astype(jnp.float32) + s * noise.astype(
            jnp.float32
        )
        return out.astype(original.dtype)

    def step(self, sample, model_output, step_index, state):
        s = _per_row(self._sigmas[step_index], sample)
        s_next = _per_row(self._sigmas[step_index + 1], sample)
        x = sample.astype(jnp.float32) + (s_next - s) * model_output.astype(
            jnp.float32
        )
        return x.astype(sample.dtype), state


SCHEDULERS = {
    "ddim": DDIMScheduler,
    "euler": EulerDiscreteScheduler,
    "dpm-solver": DPMSolverMultistepScheduler,
    "flow-euler": FlowMatchEulerScheduler,
}


def get_scheduler(name: str, **kwargs) -> BaseScheduler:
    """CLI-name factory, matching the reference's choices (run_sdxl.py:33-36)."""
    if name not in SCHEDULERS:
        raise ValueError(f"scheduler must be one of {sorted(SCHEDULERS)}, got {name!r}")
    return SCHEDULERS[name](**kwargs)
