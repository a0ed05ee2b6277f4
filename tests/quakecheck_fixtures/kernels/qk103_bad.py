"""Seeded QK103 violations (parse-only fixture; never imported): direct
pltpu compat-only name, launcher without a divisibility guard, int8 dot
without int32 accumulation, f64 inside a kernel body."""
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scale_kernel(x_ref, o_ref):
    acc = x_ref[...].astype(jnp.float64)   # QK103: f64 in kernel body
    o_ref[...] = acc.astype(jnp.float32)


def launch_scale(x):
    params = pltpu.CompilerParams()        # QK103: bypass pallas_compat
    return pl.pallas_call(                 # QK103: no divisibility guard
        _scale_kernel, out_shape=x, compiler_params=params)(x)


def dot_q8(codes, cents):
    # QK103: int8 path accumulating in the operand dtype
    return jnp.einsum("bd,pd->bp", codes, cents)
