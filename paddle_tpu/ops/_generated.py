"""GENERATED by `python -m paddle_tpu.ops.gen` from ops.yaml — do not edit.

Python bindings + registry metadata + AMP lists for the yaml-declared
op set (the reference's generated api.cc / dygraph_functions.cc role).
"""
import builtins

import numpy as np
import jax
import jax.numpy as jnp

from ..core.dispatch import dispatch
from ..core.dtypes import to_jax_dtype
from ..core.tensor import Tensor
from ._helpers import (  # noqa: F401
    _axis, _dt, _int_list, _jd, _shape)


def add(x, y, name=None):
    """elementwise_add (generated from ops.yaml)."""
    return dispatch("elementwise_add", jnp.add, (x, y), {})

def subtract(x, y, name=None):
    """elementwise_sub (generated from ops.yaml)."""
    return dispatch("elementwise_sub", jnp.subtract, (x, y), {})

def multiply(x, y, name=None):
    """elementwise_mul (generated from ops.yaml)."""
    return dispatch("elementwise_mul", jnp.multiply, (x, y), {})

def divide(x, y, name=None):
    """elementwise_div (generated from ops.yaml)."""
    return dispatch("elementwise_div", jnp.divide, (x, y), {})

def floor_divide(x, y, name=None):
    """elementwise_floordiv (generated from ops.yaml)."""
    return dispatch("elementwise_floordiv", jnp.floor_divide, (x, y), {})

def mod(x, y, name=None):
    """elementwise_mod (generated from ops.yaml)."""
    return dispatch("elementwise_mod", jnp.mod, (x, y), {})

def maximum(x, y, name=None):
    """elementwise_max (generated from ops.yaml)."""
    return dispatch("elementwise_max", jnp.maximum, (x, y), {})

def minimum(x, y, name=None):
    """elementwise_min (generated from ops.yaml)."""
    return dispatch("elementwise_min", jnp.minimum, (x, y), {})

def fmax(x, y, name=None):
    """elementwise_fmax (generated from ops.yaml)."""
    return dispatch("elementwise_fmax", jnp.fmax, (x, y), {})

def fmin(x, y, name=None):
    """elementwise_fmin (generated from ops.yaml)."""
    return dispatch("elementwise_fmin", jnp.fmin, (x, y), {})

def atan2(x, y, name=None):
    """atan2 (generated from ops.yaml)."""
    return dispatch("atan2", jnp.arctan2, (x, y), {})

def heaviside(x, y, name=None):
    """elementwise_heaviside (generated from ops.yaml)."""
    return dispatch("elementwise_heaviside", jnp.heaviside, (x, y), {})

def hypot(x, y, name=None):
    """hypot (generated from ops.yaml)."""
    return dispatch("hypot", jnp.hypot, (x, y), {})

def logaddexp(x, y, name=None):
    """logaddexp (generated from ops.yaml)."""
    return dispatch("logaddexp", jnp.logaddexp, (x, y), {})

def ldexp(x, y, name=None):
    """ldexp (generated from ops.yaml)."""
    return dispatch("ldexp", jnp.ldexp, (x, y), {})

def gcd(x, y, name=None):
    """gcd (generated from ops.yaml)."""
    return dispatch("gcd", jnp.gcd, (x, y), {}, differentiable=False)

def lcm(x, y, name=None):
    """lcm (generated from ops.yaml)."""
    return dispatch("lcm", jnp.lcm, (x, y), {}, differentiable=False)

def pow(x, y, name=None):
    """elementwise_pow (generated from ops.yaml)."""
    return dispatch("elementwise_pow", jnp.power, (x, y), {})

def sqrt(x, name=None):
    """sqrt (generated from ops.yaml)."""
    return dispatch("sqrt", jnp.sqrt, (x,), {})

def rsqrt(x, name=None):
    """rsqrt (generated from ops.yaml)."""
    return dispatch("rsqrt", jax.lax.rsqrt, (x,), {})

def square(x, name=None):
    """square (generated from ops.yaml)."""
    return dispatch("square", jnp.square, (x,), {})

def exp(x, name=None):
    """exp (generated from ops.yaml)."""
    return dispatch("exp", jnp.exp, (x,), {})

def expm1(x, name=None):
    """expm1 (generated from ops.yaml)."""
    return dispatch("expm1", jnp.expm1, (x,), {})

def log(x, name=None):
    """log (generated from ops.yaml)."""
    return dispatch("log", jnp.log, (x,), {})

def log2(x, name=None):
    """log2 (generated from ops.yaml)."""
    return dispatch("log2", jnp.log2, (x,), {})

def log10(x, name=None):
    """log10 (generated from ops.yaml)."""
    return dispatch("log10", jnp.log10, (x,), {})

def log1p(x, name=None):
    """log1p (generated from ops.yaml)."""
    return dispatch("log1p", jnp.log1p, (x,), {})

def abs(x, name=None):
    """abs (generated from ops.yaml)."""
    return dispatch("abs", jnp.abs, (x,), {})

def neg(x, name=None):
    """neg (generated from ops.yaml)."""
    return dispatch("neg", jnp.negative, (x,), {})

def sin(x, name=None):
    """sin (generated from ops.yaml)."""
    return dispatch("sin", jnp.sin, (x,), {})

def cos(x, name=None):
    """cos (generated from ops.yaml)."""
    return dispatch("cos", jnp.cos, (x,), {})

def tan(x, name=None):
    """tan (generated from ops.yaml)."""
    return dispatch("tan", jnp.tan, (x,), {})

def asin(x, name=None):
    """asin (generated from ops.yaml)."""
    return dispatch("asin", jnp.arcsin, (x,), {})

def acos(x, name=None):
    """acos (generated from ops.yaml)."""
    return dispatch("acos", jnp.arccos, (x,), {})

def atan(x, name=None):
    """atan (generated from ops.yaml)."""
    return dispatch("atan", jnp.arctan, (x,), {})

def sinh(x, name=None):
    """sinh (generated from ops.yaml)."""
    return dispatch("sinh", jnp.sinh, (x,), {})

def cosh(x, name=None):
    """cosh (generated from ops.yaml)."""
    return dispatch("cosh", jnp.cosh, (x,), {})

def tanh(x, name=None):
    """tanh (generated from ops.yaml)."""
    return dispatch("tanh", jnp.tanh, (x,), {})

def asinh(x, name=None):
    """asinh (generated from ops.yaml)."""
    return dispatch("asinh", jnp.arcsinh, (x,), {})

def acosh(x, name=None):
    """acosh (generated from ops.yaml)."""
    return dispatch("acosh", jnp.arccosh, (x,), {})

def atanh(x, name=None):
    """atanh (generated from ops.yaml)."""
    return dispatch("atanh", jnp.arctanh, (x,), {})

def floor(x, name=None):
    """floor (generated from ops.yaml)."""
    return dispatch("floor", jnp.floor, (x,), {})

def ceil(x, name=None):
    """ceil (generated from ops.yaml)."""
    return dispatch("ceil", jnp.ceil, (x,), {})

def round(x, name=None):
    """round (generated from ops.yaml)."""
    return dispatch("round", jnp.round, (x,), {})

def trunc(x, name=None):
    """trunc (generated from ops.yaml)."""
    return dispatch("trunc", jnp.trunc, (x,), {})

def reciprocal(x, name=None):
    """reciprocal (generated from ops.yaml)."""
    return dispatch("reciprocal", jnp.reciprocal, (x,), {})

def erf(x, name=None):
    """erf (generated from ops.yaml)."""
    return dispatch("erf", jax.scipy.special.erf, (x,), {})

def erfinv(x, name=None):
    """erfinv (generated from ops.yaml)."""
    return dispatch("erfinv", jax.scipy.special.erfinv, (x,), {})

def lgamma(x, name=None):
    """lgamma (generated from ops.yaml)."""
    return dispatch("lgamma", jax.scipy.special.gammaln, (x,), {})

def digamma(x, name=None):
    """digamma (generated from ops.yaml)."""
    return dispatch("digamma", jax.scipy.special.digamma, (x,), {})

def rad2deg(x, name=None):
    """rad2deg (generated from ops.yaml)."""
    return dispatch("rad2deg", jnp.rad2deg, (x,), {})

def deg2rad(x, name=None):
    """deg2rad (generated from ops.yaml)."""
    return dispatch("deg2rad", jnp.deg2rad, (x,), {})

def angle(x, name=None):
    """angle (generated from ops.yaml)."""
    return dispatch("angle", jnp.angle, (x,), {})

def conj(x, name=None):
    """conj (generated from ops.yaml)."""
    return dispatch("conj", jnp.conjugate, (x,), {})

def real(x, name=None):
    """real (generated from ops.yaml)."""
    return dispatch("real", jnp.real, (x,), {})

def imag(x, name=None):
    """imag (generated from ops.yaml)."""
    return dispatch("imag", jnp.imag, (x,), {})

def frac(x, name=None):
    """frac (generated from ops.yaml)."""
    return dispatch("frac", (lambda v: v - jnp.trunc(v)), (x,), {})

def sign(x, name=None):
    """sign (generated from ops.yaml)."""
    return dispatch("sign", jnp.sign, (x,), {}, differentiable=False)

def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    """stanh (generated from ops.yaml)."""
    return dispatch("stanh",
                    lambda v, *, a, b: b * jnp.tanh(a * v), (x,),
                    dict(a=float(scale_a), b=float(scale_b)))

def equal(x, y, name=None):
    """equal (generated from ops.yaml)."""
    return dispatch("equal", jnp.equal, (x, y), {}, differentiable=False)

def not_equal(x, y, name=None):
    """not_equal (generated from ops.yaml)."""
    return dispatch("not_equal", jnp.not_equal, (x, y), {}, differentiable=False)

def greater_than(x, y, name=None):
    """greater_than (generated from ops.yaml)."""
    return dispatch("greater_than", jnp.greater, (x, y), {}, differentiable=False)

def greater_equal(x, y, name=None):
    """greater_equal (generated from ops.yaml)."""
    return dispatch("greater_equal", jnp.greater_equal, (x, y), {}, differentiable=False)

def less_than(x, y, name=None):
    """less_than (generated from ops.yaml)."""
    return dispatch("less_than", jnp.less, (x, y), {}, differentiable=False)

def less_equal(x, y, name=None):
    """less_equal (generated from ops.yaml)."""
    return dispatch("less_equal", jnp.less_equal, (x, y), {}, differentiable=False)

def logical_and(x, y, name=None):
    """logical_and (generated from ops.yaml)."""
    return dispatch("logical_and", jnp.logical_and, (x, y), {}, differentiable=False)

def logical_or(x, y, name=None):
    """logical_or (generated from ops.yaml)."""
    return dispatch("logical_or", jnp.logical_or, (x, y), {}, differentiable=False)

def logical_xor(x, y, name=None):
    """logical_xor (generated from ops.yaml)."""
    return dispatch("logical_xor", jnp.logical_xor, (x, y), {}, differentiable=False)

def bitwise_and(x, y, name=None):
    """bitwise_and (generated from ops.yaml)."""
    return dispatch("bitwise_and", jnp.bitwise_and, (x, y), {}, differentiable=False)

def bitwise_or(x, y, name=None):
    """bitwise_or (generated from ops.yaml)."""
    return dispatch("bitwise_or", jnp.bitwise_or, (x, y), {}, differentiable=False)

def bitwise_xor(x, y, name=None):
    """bitwise_xor (generated from ops.yaml)."""
    return dispatch("bitwise_xor", jnp.bitwise_xor, (x, y), {}, differentiable=False)

def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    """matmul_v2 (generated from ops.yaml)."""
    def impl(a, b, *, tx, ty):
        if tx:
            a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a
        if ty:
            b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b
        return jnp.matmul(a, b)
    return dispatch("matmul_v2", impl, (x, y),
                dict(tx=bool(transpose_x), ty=bool(transpose_y)))

def bmm(x, y, name=None):
    """bmm (generated from ops.yaml)."""
    return dispatch("bmm", jnp.matmul, (x, y), {})

def mv(x, vec, name=None):
    """mv (generated from ops.yaml)."""
    return dispatch("mv", jnp.matmul, (x, vec), {})

def addmm(input, x, y, beta=1.0, alpha=1.0, name=None):
    """addmm (generated from ops.yaml)."""
    return dispatch(
        "addmm",
        lambda i, a, b, *, alpha, beta: beta * i + alpha * (a @ b),
        (input, x, y), dict(alpha=float(alpha), beta=float(beta)))

def inner(x, y, name=None):
    """inner (generated from ops.yaml)."""
    return dispatch("inner", jnp.inner, (x, y), {})

def mean(x, axis=None, keepdim=False, name=None):
    """reduce_mean (generated from ops.yaml)."""
    return dispatch(
        "reduce_mean",
        lambda v, *, axis, keepdims: jnp.mean(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))

def sum(x, axis=None, dtype=None, keepdim=False, name=None):
    """reduce_sum (generated from ops.yaml)."""
    def impl(v, *, axis, dtype, keepdims):
        if dtype is None and jnp.issubdtype(v.dtype, jnp.bool_):
            dtype = jnp.int64
        return jnp.sum(v, axis=axis, dtype=dtype, keepdims=keepdims)
    return dispatch(
        "reduce_sum", impl, (x,),
        dict(axis=_axis(axis), dtype=_dt(dtype),
             keepdims=bool(keepdim)))

def prod(x, axis=None, keepdim=False, dtype=None, name=None):
    """reduce_prod (generated from ops.yaml)."""
    def impl(v, *, axis, dtype, keepdims):
        return jnp.prod(v, axis=axis, dtype=dtype, keepdims=keepdims)
    return dispatch(
        "reduce_prod", impl, (x,),
        dict(axis=_axis(axis), dtype=_dt(dtype),
             keepdims=bool(keepdim)))

def logsumexp(x, axis=None, keepdim=False, name=None):
    """logsumexp (generated from ops.yaml)."""
    return dispatch(
        "logsumexp",
        lambda v, *, axis, keepdims: jax.scipy.special.logsumexp(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))

def var(x, axis=None, unbiased=True, keepdim=False, name=None):
    """variance (generated from ops.yaml)."""
    return dispatch(
        "variance",
        lambda v, *, axis, ddof, keepdims: jnp.var(
            v, axis=axis, ddof=ddof, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), ddof=1 if unbiased else 0,
                   keepdims=bool(keepdim)))

def std(x, axis=None, unbiased=True, keepdim=False, name=None):
    """std (generated from ops.yaml)."""
    return dispatch(
        "std",
        lambda v, *, axis, ddof, keepdims: jnp.std(
            v, axis=axis, ddof=ddof, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), ddof=1 if unbiased else 0,
                   keepdims=bool(keepdim)))

def allclose(x, y, rtol=1e-05, atol=1e-08, equal_nan=False, name=None):
    """allclose (generated from ops.yaml)."""
    return dispatch(
        "allclose",
        lambda a, b, *, rtol, atol, equal_nan: jnp.allclose(
            a, b, rtol=rtol, atol=atol, equal_nan=equal_nan),
        (x, y), dict(rtol=float(rtol), atol=float(atol),
                     equal_nan=bool(equal_nan)),
        differentiable=False)

def bitwise_not(x, name=None):
    """bitwise_not (generated from ops.yaml)."""
    return dispatch("bitwise_not", jnp.bitwise_not, (x,), {},
                    differentiable=False)

def equal_all(x, y, name=None):
    """equal_all (generated from ops.yaml)."""
    def impl(a, b):
        if a.shape != b.shape:
            return jnp.asarray(False)
        return jnp.all(a == b)
    return dispatch("equal_all", impl, (x, y), {}, differentiable=False)

def isclose(x, y, rtol=1e-05, atol=1e-08, equal_nan=False, name=None):
    """isclose (generated from ops.yaml)."""
    return dispatch(
        "isclose",
        lambda a, b, *, rtol, atol, equal_nan: jnp.isclose(
            a, b, rtol=rtol, atol=atol, equal_nan=equal_nan),
        (x, y), dict(rtol=float(rtol), atol=float(atol),
                     equal_nan=bool(equal_nan)),
        differentiable=False)

def isin(x, test_x, assume_unique=False, invert=False, name=None):
    """isin (generated from ops.yaml)."""
    return dispatch(
        "isin",
        lambda a, b, *, invert, uniq: jnp.isin(
            a, b, invert=invert, assume_unique=uniq),
        (x, test_x), dict(invert=bool(invert),
                          uniq=bool(assume_unique)),
        differentiable=False)

def logical_not(x, name=None):
    """logical_not (generated from ops.yaml)."""
    return dispatch("logical_not", jnp.logical_not, (x,), {},
                    differentiable=False)

def clone(x, name=None):
    """clone (generated from ops.yaml)."""
    return dispatch("clone", lambda v: jnp.copy(v), (x,), {})

def complex(real, imag, name=None):
    """complex (generated from ops.yaml)."""
    return dispatch("complex", lambda r, i: jax.lax.complex(r, i),
                    (real, imag), {})

def diagflat(x, offset=0, name=None):
    """diagflat (generated from ops.yaml)."""
    return dispatch("diagflat",
                    lambda v, *, k: jnp.diagflat(v, k), (x,),
                    dict(k=int(offset)))

def eye(num_rows, num_columns=None, dtype=None, name=None):
    """eye (generated from ops.yaml)."""
    return dispatch(
    "eye", lambda *, n, m, dtype: jnp.eye(n, m, dtype=dtype), (),
    dict(n=int(num_rows),
         m=None if num_columns is None else int(num_columns),
         dtype=_jd(dtype)))

def full_like(x, fill_value, dtype=None, name=None):
    """full_like (generated from ops.yaml)."""
    return dispatch(
    "full_like",
    lambda v, *, value, dtype: jnp.full_like(v, value, dtype), (x,),
    dict(value=fill_value,
         dtype=None if dtype is None else to_jax_dtype(dtype)),
    differentiable=False)

def linspace(start, stop, num, dtype=None, name=None):
    """linspace (generated from ops.yaml)."""
    start = start.item() if isinstance(start, Tensor) else start
    stop = stop.item() if isinstance(stop, Tensor) else stop
    num = int(num.item()) if isinstance(num, Tensor) else int(num)
    return dispatch(
    "linspace",
    lambda *, start, stop, num, dtype: jnp.linspace(
        start, stop, num, dtype=dtype),
    (), dict(start=start, stop=stop, num=num, dtype=_jd(dtype)))

def logspace(start, stop, num, base=10.0, dtype=None, name=None):
    """logspace (generated from ops.yaml)."""
    return dispatch(
    "logspace",
    lambda *, start, stop, num, base, dtype: jnp.logspace(
        start, stop, num, base=base, dtype=dtype),
    (), dict(start=float(start), stop=float(stop), num=int(num),
             base=float(base), dtype=_jd(dtype)))

def ones(shape, dtype=None, name=None):
    """ones (generated from ops.yaml)."""
    return dispatch("ones", lambda *, shape, dtype: jnp.ones(shape, dtype),
                (), dict(shape=_shape(shape), dtype=_jd(dtype)))

def ones_like(x, dtype=None, name=None):
    """ones_like (generated from ops.yaml)."""
    return dispatch("ones_like", lambda v, *, dtype: jnp.ones_like(v, dtype), (x,),
                    dict(dtype=_dt(dtype)), differentiable=False)

def tril(x, diagonal=0, name=None):
    """tril (generated from ops.yaml)."""
    return dispatch("tril", lambda v, *, k: jnp.tril(v, k), (x,),
                    dict(k=int(diagonal)))

def triu(x, diagonal=0, name=None):
    """triu (generated from ops.yaml)."""
    return dispatch("triu", lambda v, *, k: jnp.triu(v, k), (x,),
                    dict(k=int(diagonal)))

def zeros(shape, dtype=None, name=None):
    """zeros (generated from ops.yaml)."""
    return dispatch("zeros", lambda *, shape, dtype: jnp.zeros(shape, dtype),
                (), dict(shape=_shape(shape), dtype=_jd(dtype)))

def zeros_like(x, dtype=None, name=None):
    """zeros_like (generated from ops.yaml)."""
    return dispatch("zeros_like", lambda v, *, dtype: jnp.zeros_like(v, dtype), (x,),
                    dict(dtype=_dt(dtype)), differentiable=False)

def cholesky(x, upper=False, name=None):
    """cholesky (generated from ops.yaml)."""
    def impl(v, *, upper):
        L = jnp.linalg.cholesky(v)
        return jnp.swapaxes(L, -1, -2) if upper else L
    return dispatch("cholesky", impl, (x,), dict(upper=bool(upper)))

def cholesky_solve(x, y, upper=False, name=None):
    """cholesky_solve (generated from ops.yaml)."""
    def impl(b, L, *, upper):
        return jax.scipy.linalg.cho_solve((L, not upper), b)
    return dispatch("cholesky_solve", impl, (x, y), dict(upper=bool(upper)))

def corrcoef(x, rowvar=True, name=None):
    """corrcoef (generated from ops.yaml)."""
    return dispatch("corrcoef",
                    lambda v, *, rowvar: jnp.corrcoef(v, rowvar=rowvar),
                    (x,), dict(rowvar=bool(rowvar)))

def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None, name=None):
    """cov (generated from ops.yaml)."""
    ts = (x,) + tuple(w for w in (fweights, aweights) if w is not None)
    return dispatch(
        "cov",
        lambda v, *ws, rowvar, ddof, has_f, has_a: jnp.cov(
            v, rowvar=rowvar, ddof=1 if ddof else 0,
            fweights=ws[0] if has_f else None,
            aweights=ws[-1] if has_a else None),
        ts, dict(rowvar=bool(rowvar), ddof=bool(ddof),
                 has_f=fweights is not None,
                 has_a=aweights is not None))

def dot(x, y, name=None):
    """dot (generated from ops.yaml)."""
    def impl(a, b):
        return jnp.sum(a * b, axis=-1)
    return dispatch("dot", impl, (x, y), {})

def eigh(x, UPLO="L", name=None):
    """eigh (generated from ops.yaml)."""
    def impl(v, *, uplo):
        return tuple(jnp.linalg.eigh(v, symmetrize_input=True))
    return dispatch("eigh", impl, (x,), dict(uplo=UPLO))

def eigvalsh(x, UPLO="L", name=None):
    """eigvalsh (generated from ops.yaml)."""
    return dispatch("eigvalsh",
                    lambda v, *, uplo: jnp.linalg.eigvalsh(
                        v, UPLO=uplo), (x,),
                    dict(uplo=UPLO))

def lstsq(x, y, rcond=None, driver=None, name=None):
    """lstsq (generated from ops.yaml)."""
    def impl(a, b, *, rcond):
        sol, res, rank, sv = jnp.linalg.lstsq(a, b, rcond=rcond)
        return sol, res, rank.astype(jnp.int64), sv
    return dispatch("lstsq", impl, (x, y), dict(rcond=rcond))

def matrix_exp(x, name=None):
    """matrix_exp (generated from ops.yaml)."""
    return dispatch("matrix_exp", jax.scipy.linalg.expm, (x,), {})

def matrix_power(x, n, name=None):
    """matrix_power (generated from ops.yaml)."""
    return dispatch("matrix_power",
                    lambda v, *, n: jnp.linalg.matrix_power(v, n), (x,),
                    dict(n=int(n)))

def matrix_rank(x, tol=None, hermitian=False, name=None):
    """matrix_rank (generated from ops.yaml)."""
    def impl(v, *, tol):
        return jnp.linalg.matrix_rank(v, tol=tol).astype(jnp.int64)
    t_ = tol.item() if isinstance(tol, Tensor) else tol
    return dispatch("matrix_rank", impl, (x,), dict(tol=t_),
                differentiable=False)

def multi_dot(tensors, name=None):
    """multi_dot (generated from ops.yaml)."""
    return dispatch("multi_dot", lambda *vs: jnp.linalg.multi_dot(vs),
                    tuple(tensors), {})

def pinv(x, rcond=1e-15, hermitian=False, name=None):
    """pinv (generated from ops.yaml)."""
    return dispatch("pinv",
                    lambda v, *, rcond, hermitian: jnp.linalg.pinv(
                        v, rcond=rcond, hermitian=hermitian),
                    (x,), dict(rcond=float(rcond) if not isinstance(
                        rcond, Tensor) else float(rcond.item()),
                        hermitian=bool(hermitian)))

def solve(x, y, name=None):
    """solve (generated from ops.yaml)."""
    def impl(a, b):
        if b.ndim == a.ndim - 1:
            return jnp.linalg.solve(a, b[..., None])[..., 0]
        return jnp.linalg.solve(a, b)
    return dispatch("solve", impl, (x, y), {})

def svd(x, full_matrices=False, name=None):
    """svd (generated from ops.yaml)."""
    def impl(v, *, fm):
        return tuple(jnp.linalg.svd(v, full_matrices=fm))
    return dispatch("svd", impl, (x,), dict(fm=bool(full_matrices)))

def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False, name=None):
    """triangular_solve (generated from ops.yaml)."""
    def impl(a, b, *, upper, trans, unit):
        return jax.scipy.linalg.solve_triangular(
            a, b, lower=not upper, trans=1 if trans else 0,
            unit_diagonal=unit)
    return dispatch("triangular_solve", impl, (x, y),
                dict(upper=bool(upper), trans=bool(transpose),
                     unit=bool(unitriangular)))

def vander(x, n=None, increasing=False, name=None):
    """vander (generated from ops.yaml)."""
    return dispatch(
        "vander",
        lambda v, *, n, inc: jnp.vander(v, n, increasing=inc), (x,),
        dict(n=None if n is None else int(n), inc=bool(increasing)))

def vecdot(x, y, axis=-1, name=None):
    """vecdot (generated from ops.yaml)."""
    return dispatch("vecdot",
                    lambda a, b, *, axis: jnp.sum(a * b, axis=axis),
                    (x, y), dict(axis=int(axis)))

def argsort(x, axis=-1, descending=False, stable=False, name=None):
    """argsort (generated from ops.yaml)."""
    def impl(v, *, axis, desc):
        out = jnp.argsort(v, axis=axis, stable=True)
        return (jnp.flip(out, axis) if desc else out).astype(jnp.int64)
    return dispatch("argsort", impl, (x,),
                dict(axis=int(axis), desc=bool(descending)),
                differentiable=False)

def broadcast_to(x, shape, name=None):
    """broadcast_to (generated from ops.yaml)."""
    return dispatch("broadcast_to",
                lambda v, *, shape: jnp.broadcast_to(v, shape), (x,),
                dict(shape=tuple(_int_list(shape))))

def cast(x, dtype, name=None):
    """cast (generated from ops.yaml)."""
    jd = to_jax_dtype(dtype)
    return dispatch("cast", lambda v, *, dtype: jnp.asarray(v, dtype), (x,),
                dict(dtype=jd))

def column_stack(x, name=None):
    """column_stack (generated from ops.yaml)."""
    def impl(*vs):
        return jnp.column_stack(vs)
    return dispatch("column_stack", impl, tuple(x), {})

def concat(x, axis=0, name=None):
    """concat (generated from ops.yaml)."""
    axis = int(axis.item()) if isinstance(axis, Tensor) else int(axis)
    xs = builtins.list(x)
    return dispatch("concat",
                lambda *vs, axis: jnp.concatenate(vs, axis), tuple(xs),
                dict(axis=axis))

def diagonal(x, offset=0, axis1=0, axis2=1, name=None):
    """diagonal (generated from ops.yaml)."""
    return dispatch(
        "diagonal",
        lambda v, offset, axis1, axis2: jnp.diagonal(
            v, offset=offset, axis1=axis1, axis2=axis2),
        (x,), dict(offset=int(offset), axis1=int(axis1),
                   axis2=int(axis2)))

def flatten(x, start_axis=0, stop_axis=-1, name=None):
    """flatten (generated from ops.yaml)."""
    def impl(v, *, s, e):
        nd = v.ndim
        s_, e_ = s % nd if nd else 0, e % nd if nd else 0
        new_shape = v.shape[:s_] + (-1,) + v.shape[e_ + 1:]
        return jnp.reshape(v, new_shape)
    return dispatch("flatten", impl, (x,),
                dict(s=int(start_axis), e=int(stop_axis)))

def flip(x, axis, name=None):
    """flip (generated from ops.yaml)."""
    return dispatch("flip", lambda v, *, axis: jnp.flip(v, axis), (x,),
                dict(axis=tuple(_int_list(axis))))

def gather(x, index, axis=0, name=None):
    """gather (generated from ops.yaml)."""
    axis = int(axis.item()) if isinstance(axis, Tensor) else int(axis)
    def impl(v, idx, *, axis):
        return jnp.take(v, idx.reshape(-1) if idx.ndim > 1 else idx,
                        axis=axis)
    return dispatch("gather", impl, (x, index), dict(axis=axis))

def gather_nd(x, index, name=None):
    """gather_nd (generated from ops.yaml)."""
    def impl(v, idx):
        return v[tuple(jnp.moveaxis(idx, -1, 0))]
    return dispatch("gather_nd", impl, (x, index), {})

def index_add(x, index, axis, value, name=None):
    """index_add (generated from ops.yaml)."""
    def impl(v, idx, val, *, axis):
        vm = jnp.moveaxis(v, axis, 0)
        valm = jnp.moveaxis(val, axis, 0)
        out = vm.at[idx].add(valm)
        return jnp.moveaxis(out, 0, axis)
    return dispatch("index_add", impl, (x, index, value),
                dict(axis=int(axis)))

def index_fill(x, index, axis, value, name=None):
    """index_fill (generated from ops.yaml)."""
    def impl(v, idx, axis, value):
        moved = jnp.moveaxis(v, axis, 0)
        moved = moved.at[idx].set(value)
        return jnp.moveaxis(moved, 0, axis)
    return dispatch("index_fill", impl, (x, index),
                dict(axis=int(axis),
                     value=float(value) if not isinstance(
                         value, (list, tuple)) else value))

def index_put(x, indices, value, accumulate=False, name=None):
    """index_put (generated from ops.yaml)."""
    def impl(v, val, *idx, accumulate):
        if accumulate:
            return v.at[tuple(idx)].add(val)
        return v.at[tuple(idx)].set(val)
    return dispatch("index_put", impl, (x, value) + tuple(indices),
                dict(accumulate=bool(accumulate)))

def index_sample(x, index, name=None):
    """index_sample (generated from ops.yaml)."""
    def impl(v, idx):
        return jnp.take_along_axis(v, idx, axis=1)
    return dispatch("index_sample", impl, (x, index), {})

def index_select(x, index, axis=0, name=None):
    """index_select (generated from ops.yaml)."""
    def impl(v, idx, *, axis):
        return jnp.take(v, idx, axis=axis)
    return dispatch("index_select", impl, (x, index), dict(axis=int(axis)))

def masked_fill(x, mask, value, name=None):
    """masked_fill (generated from ops.yaml)."""
    def impl(v, m, *, value):
        return jnp.where(m, jnp.asarray(value, v.dtype), v)
    value = value.item() if isinstance(value, Tensor) else value
    return dispatch("masked_fill", impl, (x, mask), dict(value=value))

def moveaxis(x, source, destination, name=None):
    """moveaxis (generated from ops.yaml)."""
    return dispatch(
    "moveaxis",
    lambda v, *, s, d: jnp.moveaxis(v, s, d), (x,),
    dict(s=tuple(_int_list(source)), d=tuple(_int_list(destination))))

def reshape(x, shape, name=None):
    """reshape (generated from ops.yaml)."""
    shape = tuple(_int_list(shape))
    return dispatch("reshape", lambda v, *, shape: jnp.reshape(v, shape),
                (x,), dict(shape=shape))

def roll(x, shifts, axis=None, name=None):
    """roll (generated from ops.yaml)."""
    return dispatch(
    "roll", lambda v, *, shifts, axis: jnp.roll(v, shifts, axis), (x,),
    dict(shifts=tuple(_int_list(shifts)) if not isinstance(shifts, int)
         else int(shifts),
         axis=None if axis is None else (
             tuple(_int_list(axis)) if not isinstance(axis, int)
             else int(axis))))

def rot90(x, k=1, axes=(0, 1), name=None):
    """rot90 (generated from ops.yaml)."""
    return dispatch("rot90", lambda v, *, k, axes: jnp.rot90(v, k, axes),
                    (x,), dict(k=int(k), axes=tuple(axes)))

def row_stack(x, name=None):
    """row_stack (generated from ops.yaml)."""
    def impl(*vs):
        return jnp.vstack(vs)
    return dispatch("row_stack", impl, tuple(x), {})

def scatter(x, index, updates, overwrite=True, name=None):
    """scatter (generated from ops.yaml)."""
    def impl(v, idx, upd, *, overwrite):
        idx = idx.reshape(-1)
        if overwrite:
            return v.at[idx].set(upd)
        base = v.at[idx].set(jnp.zeros_like(upd))
        return base.at[idx].add(upd)
    return dispatch("scatter", impl, (x, index, updates),
                dict(overwrite=bool(overwrite)))

def scatter_nd(index, updates, shape, name=None):
    """scatter_nd (generated from ops.yaml)."""
    def impl(idx, upd, *, shape):
        z = jnp.zeros(shape, upd.dtype)
        return z.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)
    return dispatch("scatter_nd", impl, (index, updates),
                dict(shape=tuple(_int_list(shape))))

def scatter_nd_add(x, index, updates, name=None):
    """scatter_nd_add (generated from ops.yaml)."""
    def impl(v, idx, upd):
        return v.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)
    return dispatch("scatter_nd_add", impl, (x, index, updates), {})

def select_scatter(x, values, axis, index, name=None):
    """select_scatter (generated from ops.yaml)."""
    def impl(v, src, axis, index):
        idx = [builtins.slice(None)] * v.ndim  # `slice` op shadows builtin
        idx[axis] = index
        return v.at[tuple(idx)].set(src)
    return dispatch("select_scatter", impl, (x, values),
                dict(axis=int(axis), index=int(index)))

def shard_index(input, index_num, nshards, shard_id, ignore_value=-1, name=None):
    """shard_index (generated from ops.yaml)."""
    def impl(v, *, index_num, nshards, shard_id, ignore_value):
        size = index_num // nshards
        lo, hi = shard_id * size, (shard_id + 1) * size
        ok = (v >= lo) & (v < hi)
        return jnp.where(ok, v - lo, ignore_value)
    return dispatch("shard_index", impl, (input,),
                dict(index_num=int(index_num), nshards=int(nshards),
                     shard_id=int(shard_id),
                     ignore_value=int(ignore_value)),
                differentiable=False)

def sort(x, axis=-1, descending=False, stable=False, name=None):
    """sort (generated from ops.yaml)."""
    def impl(v, *, axis, desc):
        out = jnp.sort(v, axis=axis)
        return jnp.flip(out, axis) if desc else out
    return dispatch("sort", impl, (x,),
                dict(axis=int(axis), desc=bool(descending)))

def stack(x, axis=0, name=None):
    """stack (generated from ops.yaml)."""
    xs = builtins.list(x)
    return dispatch("stack", lambda *vs, axis: jnp.stack(vs, axis),
                tuple(xs), dict(axis=int(axis)))

def swapaxes(x, axis1, axis2, name=None):
    """swapaxes (generated from ops.yaml)."""
    return dispatch("swapaxes",
                    lambda v, *, a, b: jnp.swapaxes(v, a, b), (x,),
                    dict(a=int(axis1), b=int(axis2)))

def take_along_axis(arr, indices, axis, broadcast=True, name=None):
    """take_along_axis (generated from ops.yaml)."""
    def impl(v, idx, *, axis):
        return jnp.take_along_axis(v, idx, axis=axis)
    return dispatch("take_along_axis", impl, (arr, indices),
                dict(axis=int(axis)))

def tile(x, repeat_times, name=None):
    """tile (generated from ops.yaml)."""
    return dispatch("tile", lambda v, *, reps: jnp.tile(v, reps), (x,),
                dict(reps=tuple(_int_list(repeat_times))))

def transpose(x, perm, name=None):
    """transpose (generated from ops.yaml)."""
    perm = tuple(_int_list(perm))
    return dispatch("transpose", lambda v, *, perm: jnp.transpose(v, perm),
                (x,), dict(perm=perm))

def unsqueeze(x, axis, name=None):
    """unsqueeze (generated from ops.yaml)."""
    axes = tuple(_int_list(axis))
    return dispatch("unsqueeze",
                lambda v, *, axes: jnp.expand_dims(v, axes), (x,),
                dict(axes=axes))

def clip(x, min=None, max=None, name=None):
    """clip (generated from ops.yaml)."""
    min = min.item() if isinstance(min, Tensor) and min.size == 1 else min
    max = max.item() if isinstance(max, Tensor) and max.size == 1 else max
    return dispatch("clip", lambda v, *, lo, hi: jnp.clip(v, lo, hi), (x,),
                dict(lo=min, hi=max))

def copysign(x, y, name=None):
    """copysign (generated from ops.yaml)."""
    return dispatch("copysign", lambda a, b: jnp.copysign(a, b), (x, y),
                    {})

def cumprod(x, dim=None, dtype=None, name=None):
    """cumprod (generated from ops.yaml)."""
    def impl(v, *, axis, dtype):
        if axis is None:
            v, axis = v.reshape(-1), 0
        return jnp.cumprod(v, axis=axis, dtype=dtype)
    return dispatch(
        "cumprod", impl, (x,),
        dict(axis=None if dim is None else int(dim),
             dtype=_dt(dtype)))

def cumsum(x, axis=None, dtype=None, name=None):
    """cumsum (generated from ops.yaml)."""
    def impl(v, *, axis, dtype):
        if axis is None:
            v, axis = v.reshape(-1), 0
        return jnp.cumsum(v, axis=axis, dtype=dtype)
    return dispatch(
        "cumsum", impl, (x,),
        dict(axis=None if axis is None else int(axis),
             dtype=_dt(dtype)))

def frexp(x, name=None):
    """frexp (generated from ops.yaml)."""
    def impl(v):
        m, e = jnp.frexp(v)
        return m, e.astype(jnp.int32)
    return dispatch("frexp", impl, (x,), {}, differentiable=False)

def gammaln(x, name=None):
    """gammaln (generated from ops.yaml)."""
    return dispatch("gammaln",
                    lambda v: jax.scipy.special.gammaln(v), (x,), {})

def i0(x, name=None):
    """i0 (generated from ops.yaml)."""
    return dispatch("i0", lambda v: jax.scipy.special.i0(v), (x,), {})

def i1(x, name=None):
    """i1 (generated from ops.yaml)."""
    return dispatch("i1", lambda v: jax.scipy.special.i1(v), (x,), {})

def isfinite(x, name=None):
    """isfinite (generated from ops.yaml)."""
    return dispatch("isfinite", jnp.isfinite, (x,), {}, differentiable=False)

def isinf(x, name=None):
    """isinf (generated from ops.yaml)."""
    return dispatch("isinf", jnp.isinf, (x,), {}, differentiable=False)

def isnan(x, name=None):
    """isnan (generated from ops.yaml)."""
    return dispatch("isnan", jnp.isnan, (x,), {}, differentiable=False)

def isneginf(x, name=None):
    """isneginf (generated from ops.yaml)."""
    return dispatch("isneginf", lambda v: jnp.isneginf(v), (x,), {},
                    differentiable=False)

def isposinf(x, name=None):
    """isposinf (generated from ops.yaml)."""
    return dispatch("isposinf", lambda v: jnp.isposinf(v), (x,), {},
                    differentiable=False)

def isreal(x, name=None):
    """isreal (generated from ops.yaml)."""
    return dispatch("isreal", lambda v: jnp.isreal(v), (x,), {},
                    differentiable=False)

def kron(x, y, name=None):
    """kron (generated from ops.yaml)."""
    return dispatch("kron", jnp.kron, (x, y), {})

def lerp(x, y, weight, name=None):
    """lerp (generated from ops.yaml)."""
    return dispatch("lerp", lambda a, b, w: a + w * (b - a), (x, y, weight),
                    {})

def logcumsumexp(x, axis=None, dtype=None, name=None):
    """logcumsumexp (generated from ops.yaml)."""
    def impl(v, axis, dtype):
        if dtype is not None:
            v = v.astype(dtype)
        if axis is None:
            v, axis = v.reshape(-1), 0
        # global-max stabilization: exact in log domain, one pass
        mx = jnp.max(v, axis=axis, keepdims=True)
        return jnp.log(jnp.cumsum(jnp.exp(v - mx), axis=axis)) + mx
    return dispatch("logcumsumexp", impl, (x,),
                dict(axis=axis, dtype=None if dtype is None
                     else to_jax_dtype(dtype)))

def logit(x, eps=None, name=None):
    """logit (generated from ops.yaml)."""
    def impl(v, *, eps):
        if eps is not None:
            v = jnp.clip(v, eps, 1.0 - eps)
        return jnp.log(v) - jnp.log1p(-v)
    return dispatch("logit", impl, (x,), dict(eps=eps))

def nan_to_num(x, nan=0.0, posinf=None, neginf=None, name=None):
    """nan_to_num (generated from ops.yaml)."""
    return dispatch(
        "nan_to_num",
        lambda v, *, nan, posinf, neginf: jnp.nan_to_num(
            v, nan=nan, posinf=posinf, neginf=neginf),
        (x,), dict(nan=nan, posinf=posinf, neginf=neginf))

def nextafter(x, y, name=None):
    """nextafter (generated from ops.yaml)."""
    return dispatch("nextafter", jnp.nextafter, (x, y), {},
                    differentiable=False)

def outer(x, y, name=None):
    """outer (generated from ops.yaml)."""
    return dispatch("outer", lambda a, b: jnp.outer(a, b), (x, y), {})

def polar(abs, angle, name=None):
    """polar (generated from ops.yaml)."""
    return dispatch(
        "polar",
        lambda r, t: jax.lax.complex(r * jnp.cos(t), r * jnp.sin(t)),
        (abs, angle), {})

def polygamma(x, n, name=None):
    """polygamma (generated from ops.yaml)."""
    return dispatch("polygamma",
                    lambda v, n: jax.scipy.special.polygamma(n, v),
                    (x,), dict(n=int(n)))

def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    """scale (generated from ops.yaml)."""
    def impl(v, s, *, bias, after):
        s = jnp.asarray(s, v.dtype)
        return v * s + bias if after else (v + bias) * s
    s = scale if isinstance(scale, Tensor) else float(scale)
    return dispatch("scale", impl, (x, s),
                dict(bias=float(bias), after=bool(bias_after_scale)))

def signbit(x, name=None):
    """signbit (generated from ops.yaml)."""
    return dispatch("signbit", lambda v: jnp.signbit(v), (x,), {},
                    differentiable=False)

def sinc(x, name=None):
    """sinc (generated from ops.yaml)."""
    return dispatch("sinc", lambda v: jnp.sinc(v), (x,), {})

def take(x, index, mode="raise", name=None):
    """take (generated from ops.yaml)."""
    def impl(v, idx, *, mode):
        flat = v.reshape(-1)
        if mode == "wrap":
            idx = jnp.mod(idx, flat.shape[0])
        elif mode == "clip":
            idx = jnp.clip(idx, 0, flat.shape[0] - 1)
        return flat[idx]
    return dispatch("take", impl, (x, index), dict(mode=mode))

def trace(x, offset=0, axis1=0, axis2=1, name=None):
    """trace (generated from ops.yaml)."""
    return dispatch(
        "trace",
        lambda v, *, k, a1, a2: jnp.trace(v, k, a1, a2), (x,),
        dict(k=int(offset), a1=int(axis1), a2=int(axis2)))

def trapezoid(y, x=None, dx=None, axis=-1, name=None):
    """trapezoid (generated from ops.yaml)."""
    def impl(yv, *maybe_x, dx, axis):
        xv = maybe_x[0] if maybe_x else None
        return jnp.trapezoid(yv, x=xv, dx=1.0 if dx is None else dx,
                             axis=axis)
    args = (y, x) if x is not None else (y,)
    return dispatch("trapezoid", impl, args, dict(dx=dx, axis=axis))

def count_nonzero(x, axis=None, keepdim=False, name=None):
    """count_nonzero (generated from ops.yaml)."""
    return dispatch(
        "count_nonzero",
        lambda v, *, axis, keepdims: jnp.count_nonzero(
            v, axis=axis, keepdims=keepdims).astype(jnp.int64),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)), differentiable=False)

def kthvalue(x, k, axis=-1, keepdim=False, name=None):
    """kthvalue (generated from ops.yaml)."""
    def impl(v, *, k, axis, keepdims):
        srt = jnp.sort(v, axis=axis)
        idxs = jnp.argsort(v, axis=axis, stable=True)
        val = jnp.take(srt, k - 1, axis=axis)
        idx = jnp.take(idxs, k - 1, axis=axis)
        if keepdims:
            val = jnp.expand_dims(val, axis)
            idx = jnp.expand_dims(idx, axis)
        return val, idx.astype(jnp.int64)
    return dispatch("kthvalue", impl, (x,),
                dict(k=int(k), axis=int(axis), keepdims=bool(keepdim)))

def median(x, axis=None, keepdim=False, mode="avg", name=None):
    """median (generated from ops.yaml)."""
    def impl(v, *, axis, keepdims, mode):
        if mode == "avg":
            return jnp.median(v, axis=axis, keepdims=keepdims)
        # 'min' mode: lower of the two middle values + its index
        if axis is None:
            vf = v.reshape(-1)
            ax = 0
        else:
            vf, ax = v, axis
        n = vf.shape[ax]
        k = (n - 1) // 2
        srt = jnp.sort(vf, axis=ax)
        val = jnp.take(srt, k, axis=ax)
        if keepdims:
            val = jnp.expand_dims(val, ax if axis is not None else ())
        return val
    return dispatch("median", impl, (x,),
                dict(axis=None if axis is None else int(axis),
                     keepdims=bool(keepdim), mode=mode))

def nanmean(x, axis=None, keepdim=False, name=None):
    """nanmean (generated from ops.yaml)."""
    return dispatch(
        "nanmean",
        lambda v, *, axis, keepdims: jnp.nanmean(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))

def nanmedian(x, axis=None, keepdim=False, mode="avg", name=None):
    """nanmedian (generated from ops.yaml)."""
    return dispatch(
        "nanmedian",
        lambda v, *, axis, keepdims: jnp.nanmedian(v, axis=axis,
                                                   keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))

def nanquantile(x, q, axis=None, keepdim=False, interpolation="linear", name=None):
    """nanquantile (generated from ops.yaml)."""
    qv = q if not isinstance(q, Tensor) else q.numpy()
    return dispatch(
    "nanquantile",
    lambda v, *, q, axis, keepdims, method: jnp.nanquantile(
        v.astype(jnp.float32), jnp.asarray(q), axis=axis,
        keepdims=keepdims, method=method),
    (x,), dict(q=qv, axis=_axis(axis), keepdims=bool(keepdim),
               method=interpolation))

def nansum(x, axis=None, dtype=None, keepdim=False, name=None):
    """nansum (generated from ops.yaml)."""
    def impl(v, *, axis, dtype, keepdims):
        return jnp.nansum(v, axis=axis, dtype=dtype, keepdims=keepdims)
    return dispatch(
        "nansum", impl, (x,),
        dict(axis=_axis(axis), dtype=_dt(dtype),
             keepdims=bool(keepdim)))

def all(x, axis=None, keepdim=False, name=None):
    """reduce_all (generated from ops.yaml)."""
    return dispatch(
        "reduce_all",
        lambda v, *, axis, keepdims: jnp.all(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)), differentiable=False)

def any(x, axis=None, keepdim=False, name=None):
    """reduce_any (generated from ops.yaml)."""
    return dispatch(
        "reduce_any",
        lambda v, *, axis, keepdims: jnp.any(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)), differentiable=False)

def max(x, axis=None, keepdim=False, name=None):
    """reduce_max (generated from ops.yaml)."""
    return dispatch(
        "reduce_max",
        lambda v, *, axis, keepdims: jnp.max(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))

def min(x, axis=None, keepdim=False, name=None):
    """reduce_min (generated from ops.yaml)."""
    return dispatch(
        "reduce_min",
        lambda v, *, axis, keepdims: jnp.min(
            v, axis=axis, keepdims=keepdims),
        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))


GENERATED_APIS = ['add', 'subtract', 'multiply', 'divide', 'floor_divide', 'mod', 'maximum', 'minimum', 'fmax', 'fmin', 'atan2', 'heaviside', 'hypot', 'logaddexp', 'ldexp', 'gcd', 'lcm', 'pow', 'sqrt', 'rsqrt', 'square', 'exp', 'expm1', 'log', 'log2', 'log10', 'log1p', 'abs', 'neg', 'sin', 'cos', 'tan', 'asin', 'acos', 'atan', 'sinh', 'cosh', 'tanh', 'asinh', 'acosh', 'atanh', 'floor', 'ceil', 'round', 'trunc', 'reciprocal', 'erf', 'erfinv', 'lgamma', 'digamma', 'rad2deg', 'deg2rad', 'angle', 'conj', 'real', 'imag', 'frac', 'sign', 'stanh', 'equal', 'not_equal', 'greater_than', 'greater_equal', 'less_than', 'less_equal', 'logical_and', 'logical_or', 'logical_xor', 'bitwise_and', 'bitwise_or', 'bitwise_xor', 'matmul', 'bmm', 'mv', 'addmm', 'inner', 'mean', 'sum', 'prod', 'logsumexp', 'var', 'std', 'allclose', 'bitwise_not', 'equal_all', 'isclose', 'isin', 'logical_not', 'clone', 'complex', 'diagflat', 'eye', 'full_like', 'linspace', 'logspace', 'ones', 'ones_like', 'tril', 'triu', 'zeros', 'zeros_like', 'cholesky', 'cholesky_solve', 'corrcoef', 'cov', 'dot', 'eigh', 'eigvalsh', 'lstsq', 'matrix_exp', 'matrix_power', 'matrix_rank', 'multi_dot', 'pinv', 'solve', 'svd', 'triangular_solve', 'vander', 'vecdot', 'argsort', 'broadcast_to', 'cast', 'column_stack', 'concat', 'diagonal', 'flatten', 'flip', 'gather', 'gather_nd', 'index_add', 'index_fill', 'index_put', 'index_sample', 'index_select', 'masked_fill', 'moveaxis', 'reshape', 'roll', 'rot90', 'row_stack', 'scatter', 'scatter_nd', 'scatter_nd_add', 'select_scatter', 'shard_index', 'sort', 'stack', 'swapaxes', 'take_along_axis', 'tile', 'transpose', 'unsqueeze', 'clip', 'copysign', 'cumprod', 'cumsum', 'frexp', 'gammaln', 'i0', 'i1', 'isfinite', 'isinf', 'isnan', 'isneginf', 'isposinf', 'isreal', 'kron', 'lerp', 'logcumsumexp', 'logit', 'nan_to_num', 'nextafter', 'outer', 'polar', 'polygamma', 'scale', 'signbit', 'sinc', 'take', 'trace', 'trapezoid', 'count_nonzero', 'kthvalue', 'median', 'nanmean', 'nanmedian', 'nanquantile', 'nansum', 'all', 'any', 'max', 'min']

AMP_WHITE_LIST = set(['addmm', 'bmm', 'conv1d', 'conv1d_transpose', 'conv2d', 'conv2d_transpose', 'conv3d', 'conv3d_transpose', 'einsum', 'inner', 'linear', 'linear_act', 'linear_act_int8', 'lora_segment_act', 'matmul_v2', 'mm', 'mv', 'scaled_dot_product_attention'])

AMP_BLACK_LIST = set(['batch_norm', 'c_softmax_with_cross_entropy', 'cross_entropy', 'elementwise_pow', 'erf', 'exp', 'fused_residual_layer_norm', 'fused_residual_layer_norm_drop', 'group_norm', 'hsigmoid_loss', 'instance_norm', 'layer_norm', 'log', 'log10', 'log1p', 'log2', 'log_softmax', 'logsumexp', 'multi_margin_loss', 'p_norm', 'reduce_mean', 'reduce_prod', 'reduce_sum', 'rms_norm', 'softmax', 'softmax_with_cross_entropy', 'square', 'std', 'variance'])

CUSTOM_VJP_OPS = set(['c_softmax_with_cross_entropy', 'cross_entropy', 'fused_residual_layer_norm', 'fused_residual_layer_norm_drop', 'layer_norm', 'linear_act', 'linear_act_int8', 'lora_segment_act', 'rms_norm', 'scaled_dot_product_attention', 'softmax_with_cross_entropy'])

__SCHEMA__ = [{'api': 'add', 'op': 'elementwise_add', 'kind': 'binary', 'impl': 'jnp.add'}, {'api': 'subtract', 'op': 'elementwise_sub', 'kind': 'binary', 'impl': 'jnp.subtract'}, {'api': 'multiply', 'op': 'elementwise_mul', 'kind': 'binary', 'impl': 'jnp.multiply'}, {'api': 'divide', 'op': 'elementwise_div', 'kind': 'binary', 'impl': 'jnp.divide'}, {'api': 'floor_divide', 'op': 'elementwise_floordiv', 'kind': 'binary', 'impl': 'jnp.floor_divide'}, {'api': 'mod', 'op': 'elementwise_mod', 'kind': 'binary', 'impl': 'jnp.mod'}, {'api': 'maximum', 'op': 'elementwise_max', 'kind': 'binary', 'impl': 'jnp.maximum'}, {'api': 'minimum', 'op': 'elementwise_min', 'kind': 'binary', 'impl': 'jnp.minimum'}, {'api': 'fmax', 'op': 'elementwise_fmax', 'kind': 'binary', 'impl': 'jnp.fmax'}, {'api': 'fmin', 'op': 'elementwise_fmin', 'kind': 'binary', 'impl': 'jnp.fmin'}, {'api': 'atan2', 'op': 'atan2', 'kind': 'binary', 'impl': 'jnp.arctan2'}, {'api': 'heaviside', 'op': 'elementwise_heaviside', 'kind': 'binary', 'impl': 'jnp.heaviside'}, {'api': 'hypot', 'op': 'hypot', 'kind': 'binary', 'impl': 'jnp.hypot'}, {'api': 'logaddexp', 'op': 'logaddexp', 'kind': 'binary', 'impl': 'jnp.logaddexp'}, {'api': 'ldexp', 'op': 'ldexp', 'kind': 'binary', 'impl': 'jnp.ldexp'}, {'api': 'gcd', 'op': 'gcd', 'kind': 'binary', 'impl': 'jnp.gcd', 'differentiable': False}, {'api': 'lcm', 'op': 'lcm', 'kind': 'binary', 'impl': 'jnp.lcm', 'differentiable': False}, {'api': 'pow', 'op': 'elementwise_pow', 'kind': 'binary', 'impl': 'jnp.power', 'amp': 'black'}, {'api': 'sqrt', 'op': 'sqrt', 'kind': 'unary', 'impl': 'jnp.sqrt'}, {'api': 'rsqrt', 'op': 'rsqrt', 'kind': 'unary', 'impl': 'jax.lax.rsqrt'}, {'api': 'square', 'op': 'square', 'kind': 'unary', 'impl': 'jnp.square', 'amp': 'black'}, {'api': 'exp', 'op': 'exp', 'kind': 'unary', 'impl': 'jnp.exp', 'amp': 'black'}, {'api': 'expm1', 'op': 'expm1', 'kind': 'unary', 'impl': 'jnp.expm1'}, {'api': 'log', 'op': 'log', 'kind': 'unary', 'impl': 'jnp.log', 'amp': 'black'}, {'api': 'log2', 'op': 'log2', 'kind': 'unary', 'impl': 'jnp.log2', 'amp': 'black'}, {'api': 'log10', 'op': 'log10', 'kind': 'unary', 'impl': 'jnp.log10', 'amp': 'black'}, {'api': 'log1p', 'op': 'log1p', 'kind': 'unary', 'impl': 'jnp.log1p', 'amp': 'black'}, {'api': 'abs', 'op': 'abs', 'kind': 'unary', 'impl': 'jnp.abs'}, {'api': 'neg', 'op': 'neg', 'kind': 'unary', 'impl': 'jnp.negative'}, {'api': 'sin', 'op': 'sin', 'kind': 'unary', 'impl': 'jnp.sin'}, {'api': 'cos', 'op': 'cos', 'kind': 'unary', 'impl': 'jnp.cos'}, {'api': 'tan', 'op': 'tan', 'kind': 'unary', 'impl': 'jnp.tan'}, {'api': 'asin', 'op': 'asin', 'kind': 'unary', 'impl': 'jnp.arcsin'}, {'api': 'acos', 'op': 'acos', 'kind': 'unary', 'impl': 'jnp.arccos'}, {'api': 'atan', 'op': 'atan', 'kind': 'unary', 'impl': 'jnp.arctan'}, {'api': 'sinh', 'op': 'sinh', 'kind': 'unary', 'impl': 'jnp.sinh'}, {'api': 'cosh', 'op': 'cosh', 'kind': 'unary', 'impl': 'jnp.cosh'}, {'api': 'tanh', 'op': 'tanh', 'kind': 'unary', 'impl': 'jnp.tanh'}, {'api': 'asinh', 'op': 'asinh', 'kind': 'unary', 'impl': 'jnp.arcsinh'}, {'api': 'acosh', 'op': 'acosh', 'kind': 'unary', 'impl': 'jnp.arccosh'}, {'api': 'atanh', 'op': 'atanh', 'kind': 'unary', 'impl': 'jnp.arctanh'}, {'api': 'floor', 'op': 'floor', 'kind': 'unary', 'impl': 'jnp.floor'}, {'api': 'ceil', 'op': 'ceil', 'kind': 'unary', 'impl': 'jnp.ceil'}, {'api': 'round', 'op': 'round', 'kind': 'unary', 'impl': 'jnp.round'}, {'api': 'trunc', 'op': 'trunc', 'kind': 'unary', 'impl': 'jnp.trunc'}, {'api': 'reciprocal', 'op': 'reciprocal', 'kind': 'unary', 'impl': 'jnp.reciprocal'}, {'api': 'erf', 'op': 'erf', 'kind': 'unary', 'impl': 'jax.scipy.special.erf', 'amp': 'black'}, {'api': 'erfinv', 'op': 'erfinv', 'kind': 'unary', 'impl': 'jax.scipy.special.erfinv'}, {'api': 'lgamma', 'op': 'lgamma', 'kind': 'unary', 'impl': 'jax.scipy.special.gammaln'}, {'api': 'digamma', 'op': 'digamma', 'kind': 'unary', 'impl': 'jax.scipy.special.digamma'}, {'api': 'rad2deg', 'op': 'rad2deg', 'kind': 'unary', 'impl': 'jnp.rad2deg'}, {'api': 'deg2rad', 'op': 'deg2rad', 'kind': 'unary', 'impl': 'jnp.deg2rad'}, {'api': 'angle', 'op': 'angle', 'kind': 'unary', 'impl': 'jnp.angle'}, {'api': 'conj', 'op': 'conj', 'kind': 'unary', 'impl': 'jnp.conjugate'}, {'api': 'real', 'op': 'real', 'kind': 'unary', 'impl': 'jnp.real'}, {'api': 'imag', 'op': 'imag', 'kind': 'unary', 'impl': 'jnp.imag'}, {'api': 'frac', 'op': 'frac', 'kind': 'unary', 'impl': 'lambda v: v - jnp.trunc(v)'}, {'api': 'sign', 'op': 'sign', 'kind': 'unary', 'impl': 'jnp.sign', 'differentiable': False}, {'api': 'stanh', 'op': 'stanh', 'kind': 'sig', 'sig': 'x, scale_a=0.67, scale_b=1.7159', 'expr': 'dispatch("stanh",\n                    lambda v, *, a, b: b * jnp.tanh(a * v), (x,),\n                    dict(a=float(scale_a), b=float(scale_b)))\n'}, {'api': 'equal', 'op': 'equal', 'kind': 'compare', 'impl': 'jnp.equal'}, {'api': 'not_equal', 'op': 'not_equal', 'kind': 'compare', 'impl': 'jnp.not_equal'}, {'api': 'greater_than', 'op': 'greater_than', 'kind': 'compare', 'impl': 'jnp.greater'}, {'api': 'greater_equal', 'op': 'greater_equal', 'kind': 'compare', 'impl': 'jnp.greater_equal'}, {'api': 'less_than', 'op': 'less_than', 'kind': 'compare', 'impl': 'jnp.less'}, {'api': 'less_equal', 'op': 'less_equal', 'kind': 'compare', 'impl': 'jnp.less_equal'}, {'api': 'logical_and', 'op': 'logical_and', 'kind': 'compare', 'impl': 'jnp.logical_and'}, {'api': 'logical_or', 'op': 'logical_or', 'kind': 'compare', 'impl': 'jnp.logical_or'}, {'api': 'logical_xor', 'op': 'logical_xor', 'kind': 'compare', 'impl': 'jnp.logical_xor'}, {'api': 'bitwise_and', 'op': 'bitwise_and', 'kind': 'compare', 'impl': 'jnp.bitwise_and'}, {'api': 'bitwise_or', 'op': 'bitwise_or', 'kind': 'compare', 'impl': 'jnp.bitwise_or'}, {'api': 'bitwise_xor', 'op': 'bitwise_xor', 'kind': 'compare', 'impl': 'jnp.bitwise_xor'}, {'api': 'matmul', 'op': 'matmul_v2', 'kind': 'sig', 'amp': 'white', 'sig': 'x, y, transpose_x=False, transpose_y=False', 'prelude': 'def impl(a, b, *, tx, ty):\n    if tx:\n        a = jnp.swapaxes(a, -1, -2) if a.ndim > 1 else a\n    if ty:\n        b = jnp.swapaxes(b, -1, -2) if b.ndim > 1 else b\n    return jnp.matmul(a, b)\n', 'expr': 'dispatch("matmul_v2", impl, (x, y),\n                dict(tx=bool(transpose_x), ty=bool(transpose_y)))\n'}, {'api': 'mm', 'op': 'mm', 'kind': 'manual', 'amp': 'white'}, {'api': 'bmm', 'op': 'bmm', 'kind': 'sig', 'amp': 'white', 'sig': 'x, y', 'expr': 'dispatch("bmm", jnp.matmul, (x, y), {})\n'}, {'api': 'mv', 'op': 'mv', 'kind': 'sig', 'amp': 'white', 'sig': 'x, vec', 'expr': 'dispatch("mv", jnp.matmul, (x, vec), {})\n'}, {'api': 'linear', 'op': 'linear', 'kind': 'manual', 'amp': 'white'}, {'api': 'linear_act', 'op': 'linear_act', 'kind': 'manual', 'amp': 'white', 'vjp': 'custom'}, {'api': 'linear_act_int8', 'op': 'linear_act_int8', 'kind': 'manual', 'amp': 'white', 'vjp': 'custom'}, {'api': 'lora_segment_act', 'op': 'lora_segment_act', 'kind': 'manual', 'amp': 'white', 'vjp': 'custom'}, {'api': 'conv1d', 'op': 'conv1d', 'kind': 'manual', 'amp': 'white'}, {'api': 'conv2d', 'op': 'conv2d', 'kind': 'manual', 'amp': 'white'}, {'api': 'conv3d', 'op': 'conv3d', 'kind': 'manual', 'amp': 'white'}, {'api': 'conv1d_transpose', 'op': 'conv1d_transpose', 'kind': 'manual', 'amp': 'white'}, {'api': 'conv2d_transpose', 'op': 'conv2d_transpose', 'kind': 'manual', 'amp': 'white'}, {'api': 'conv3d_transpose', 'op': 'conv3d_transpose', 'kind': 'manual', 'amp': 'white'}, {'api': 'einsum', 'op': 'einsum', 'kind': 'manual', 'amp': 'white'}, {'api': 'scaled_dot_product_attention', 'op': 'scaled_dot_product_attention', 'kind': 'manual', 'amp': 'white', 'vjp': 'custom'}, {'api': 'addmm', 'op': 'addmm', 'kind': 'sig', 'amp': 'white', 'sig': 'input, x, y, beta=1.0, alpha=1.0', 'expr': 'dispatch(\n        "addmm",\n        lambda i, a, b, *, alpha, beta: beta * i + alpha * (a @ b),\n        (input, x, y), dict(alpha=float(alpha), beta=float(beta)))\n'}, {'api': 'inner', 'op': 'inner', 'kind': 'sig', 'amp': 'white', 'sig': 'x, y', 'expr': 'dispatch("inner", jnp.inner, (x, y), {})\n'}, {'api': 'mean', 'op': 'reduce_mean', 'kind': 'reduction', 'amp': 'black', 'impl': 'jnp.mean'}, {'api': 'sum', 'op': 'reduce_sum', 'kind': 'reduction', 'amp': 'black', 'impl': 'jnp.sum', 'dtype': True, 'bool_promote': True}, {'api': 'softmax', 'op': 'softmax', 'kind': 'manual', 'amp': 'black'}, {'api': 'log_softmax', 'op': 'log_softmax', 'kind': 'manual', 'amp': 'black'}, {'api': 'softmax_with_cross_entropy', 'op': 'softmax_with_cross_entropy', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'cross_entropy', 'op': 'cross_entropy', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'c_softmax_with_cross_entropy', 'op': 'c_softmax_with_cross_entropy', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'layer_norm', 'op': 'layer_norm', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'fused_residual_layer_norm', 'op': 'fused_residual_layer_norm', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'fused_residual_layer_norm', 'op': 'fused_residual_layer_norm_drop', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'rms_norm', 'op': 'rms_norm', 'kind': 'manual', 'amp': 'black', 'vjp': 'custom'}, {'api': 'batch_norm', 'op': 'batch_norm', 'kind': 'manual', 'amp': 'black'}, {'api': 'group_norm', 'op': 'group_norm', 'kind': 'manual', 'amp': 'black'}, {'api': 'instance_norm', 'op': 'instance_norm', 'kind': 'manual', 'amp': 'black'}, {'api': 'p_norm', 'op': 'p_norm', 'kind': 'manual', 'amp': 'black'}, {'api': 'prod', 'op': 'reduce_prod', 'kind': 'reduction', 'amp': 'black', 'impl': 'jnp.prod', 'dtype': True, 'keepdim_first': True}, {'api': 'logsumexp', 'op': 'logsumexp', 'kind': 'reduction', 'amp': 'black', 'impl': 'jax.scipy.special.logsumexp'}, {'api': 'var', 'op': 'variance', 'kind': 'sig', 'amp': 'black', 'sig': 'x, axis=None, unbiased=True, keepdim=False', 'expr': 'dispatch(\n        "variance",\n        lambda v, *, axis, ddof, keepdims: jnp.var(\n            v, axis=axis, ddof=ddof, keepdims=keepdims),\n        (x,), dict(axis=_axis(axis), ddof=1 if unbiased else 0,\n                   keepdims=bool(keepdim)))\n'}, {'api': 'std', 'op': 'std', 'kind': 'sig', 'amp': 'black', 'sig': 'x, axis=None, unbiased=True, keepdim=False', 'expr': 'dispatch(\n        "std",\n        lambda v, *, axis, ddof, keepdims: jnp.std(\n            v, axis=axis, ddof=ddof, keepdims=keepdims),\n        (x,), dict(axis=_axis(axis), ddof=1 if unbiased else 0,\n                   keepdims=bool(keepdim)))\n'}, {'api': 'barrier', 'op': 'barrier', 'kind': 'manual'}, {'api': 'c_allgather', 'op': 'c_allgather', 'kind': 'manual'}, {'api': 'c_allreduce', 'op': 'c_allreduce', 'kind': 'manual'}, {'api': 'c_alltoall', 'op': 'c_alltoall', 'kind': 'manual'}, {'api': 'c_alltoall_single', 'op': 'c_alltoall_single', 'kind': 'manual'}, {'api': 'c_broadcast', 'op': 'c_broadcast', 'kind': 'manual'}, {'api': 'c_reducescatter', 'op': 'c_reducescatter', 'kind': 'manual'}, {'api': 'c_scatter', 'op': 'c_scatter', 'kind': 'manual'}, {'api': 'recv_v2', 'op': 'recv_v2', 'kind': 'manual'}, {'api': 'send_v2', 'op': 'send_v2', 'kind': 'manual'}, {'api': 'sharding_constraint', 'op': 'sharding_constraint', 'kind': 'manual'}, {'api': 'recompute', 'op': 'recompute', 'kind': 'manual'}, {'api': 'bernoulli_log_prob', 'op': 'bernoulli_log_prob', 'kind': 'manual'}, {'api': 'beta_log_prob', 'op': 'beta_log_prob', 'kind': 'manual'}, {'api': 'categorical_log_prob', 'op': 'categorical_log_prob', 'kind': 'manual'}, {'api': 'dirichlet_log_prob', 'op': 'dirichlet_log_prob', 'kind': 'manual'}, {'api': 'exponential_log_prob', 'op': 'exponential_log_prob', 'kind': 'manual'}, {'api': 'gamma_log_prob', 'op': 'gamma_log_prob', 'kind': 'manual'}, {'api': 'geometric_log_prob', 'op': 'geometric_log_prob', 'kind': 'manual'}, {'api': 'gumbel_log_prob', 'op': 'gumbel_log_prob', 'kind': 'manual'}, {'api': 'laplace_log_prob', 'op': 'laplace_log_prob', 'kind': 'manual'}, {'api': 'lognormal_cdf', 'op': 'lognormal_cdf', 'kind': 'manual'}, {'api': 'lognormal_log_prob', 'op': 'lognormal_log_prob', 'kind': 'manual'}, {'api': 'multinomial_log_prob', 'op': 'multinomial_log_prob', 'kind': 'manual'}, {'api': 'normal_cdf', 'op': 'normal_cdf', 'kind': 'manual'}, {'api': 'normal_log_prob', 'op': 'normal_log_prob', 'kind': 'manual'}, {'api': 'poisson_log_prob', 'op': 'poisson_log_prob', 'kind': 'manual'}, {'api': 'uniform_log_prob', 'op': 'uniform_log_prob', 'kind': 'manual'}, {'api': 'fftshift', 'op': 'fftshift', 'kind': 'manual'}, {'api': 'ifftshift', 'op': 'ifftshift', 'kind': 'manual'}, {'api': 'moe_gate', 'op': 'moe_gate', 'kind': 'manual'}, {'api': 'moe_combine', 'op': 'moe_combine', 'kind': 'manual'}, {'api': 'moe_dispatch', 'op': 'moe_dispatch', 'kind': 'manual'}, {'api': 'moe_ep', 'op': 'moe_ep', 'kind': 'manual'}, {'api': 'moe_mlp_dropless', 'op': 'moe_mlp_dropless', 'kind': 'manual'}, {'api': 'fused_bias_act', 'op': 'fused_bias_act', 'kind': 'manual'}, {'api': 'fused_gemm_epilogue', 'op': 'fused_gemm_epilogue', 'kind': 'manual'}, {'api': 'fused_linear_activation', 'op': 'fused_linear_activation', 'kind': 'manual'}, {'api': 'fused_rope', 'op': 'fused_rope', 'kind': 'manual'}, {'api': 'swiglu', 'op': 'swiglu', 'kind': 'manual'}, {'api': 'top_p_sampling', 'op': 'top_p_sampling', 'kind': 'manual', 'differentiable': False}, {'api': 'kv_cache_scatter', 'op': 'kv_cache_scatter', 'kind': 'manual', 'differentiable': False}, {'api': 'kv_cache_scatter_quant', 'op': 'kv_cache_scatter_quant', 'kind': 'manual', 'differentiable': False}, {'api': 'ragged_paged_attention', 'op': 'ragged_paged_attention', 'kind': 'manual', 'differentiable': False}, {'api': 'sparse_paged_attention', 'op': 'sparse_paged_attention', 'kind': 'manual', 'differentiable': False}, {'api': 'sparse_attention_dense', 'op': 'sparse_attention_dense', 'kind': 'manual', 'differentiable': False}, {'api': 'lightning_state_update', 'op': 'lightning_state_update', 'kind': 'manual', 'differentiable': False}, {'api': 'lightning_dense', 'op': 'lightning_dense', 'kind': 'manual', 'differentiable': False}, {'api': 'rope_tables', 'op': 'rope_tables', 'kind': 'manual', 'differentiable': False}, {'api': 'grouped_paged_attention', 'op': 'grouped_paged_attention', 'kind': 'manual', 'differentiable': False}, {'api': 'ragged_carried_rows', 'op': 'ragged_carried_rows', 'kind': 'manual', 'differentiable': False}, {'api': 'stack_reports', 'op': 'stack_reports', 'kind': 'manual', 'differentiable': False}, {'api': 'afmoe_attention_dense', 'op': 'afmoe_attention_dense', 'kind': 'manual', 'differentiable': False}, {'api': 'afmoe_routed_experts', 'op': 'afmoe_routed_experts', 'kind': 'manual', 'differentiable': False}, {'api': 'gated_delta_state_update', 'op': 'gated_delta_state_update', 'kind': 'manual', 'differentiable': False}, {'api': 'gated_delta_dense', 'op': 'gated_delta_dense', 'kind': 'manual', 'differentiable': False}, {'api': 'gated_delta_decay', 'op': 'gated_delta_decay', 'kind': 'manual', 'differentiable': False}, {'api': 'gated_rms_norm', 'op': 'gated_rms_norm', 'kind': 'manual', 'differentiable': False}, {'api': 'zero_centred_rms_norm', 'op': 'zero_centred_rms_norm', 'kind': 'manual', 'differentiable': False}, {'api': 'partial_rope', 'op': 'partial_rope', 'kind': 'manual', 'differentiable': False}, {'api': 'qwen3_next_attention_dense', 'op': 'qwen3_next_attention_dense', 'kind': 'manual', 'differentiable': False}, {'api': 'qwen3_next_routed_experts', 'op': 'qwen3_next_routed_experts', 'kind': 'manual', 'differentiable': False}, {'api': 'latent_paged_attention', 'op': 'latent_paged_attention', 'kind': 'manual', 'differentiable': False}, {'api': 'longcat_rms_norm', 'op': 'longcat_rms_norm', 'kind': 'manual', 'differentiable': False}, {'api': 'mla_rope', 'op': 'mla_rope', 'kind': 'manual', 'differentiable': False}, {'api': 'mla_absorb', 'op': 'mla_absorb', 'kind': 'manual', 'differentiable': False}, {'api': 'mla_dense', 'op': 'mla_dense', 'kind': 'manual', 'differentiable': False}, {'api': 'mla_values', 'op': 'mla_values', 'kind': 'manual', 'differentiable': False}, {'api': 'longcat_routed_experts', 'op': 'longcat_routed_experts', 'kind': 'manual', 'differentiable': False}, {'api': 'ragged_sample_next', 'op': 'ragged_sample_next', 'kind': 'manual', 'differentiable': False}, {'api': 'clip_by_global_norm', 'op': 'clip_by_global_norm', 'kind': 'manual'}, {'api': 'celu', 'op': 'celu', 'kind': 'manual'}, {'api': 'elu', 'op': 'elu', 'kind': 'manual'}, {'api': 'gelu', 'op': 'gelu', 'kind': 'manual'}, {'api': 'glu', 'op': 'glu', 'kind': 'manual'}, {'api': 'gumbel_softmax', 'op': 'gumbel_softmax', 'kind': 'manual'}, {'api': 'hard_shrink', 'op': 'hard_shrink', 'kind': 'manual'}, {'api': 'hard_sigmoid', 'op': 'hard_sigmoid', 'kind': 'manual'}, {'api': 'hard_swish', 'op': 'hard_swish', 'kind': 'manual'}, {'api': 'hard_tanh', 'op': 'hard_tanh', 'kind': 'manual'}, {'api': 'leaky_relu', 'op': 'leaky_relu', 'kind': 'manual'}, {'api': 'logsigmoid', 'op': 'logsigmoid', 'kind': 'manual'}, {'api': 'maxout', 'op': 'maxout', 'kind': 'manual'}, {'api': 'mish', 'op': 'mish', 'kind': 'manual'}, {'api': 'prelu', 'op': 'prelu', 'kind': 'manual'}, {'api': 'relu', 'op': 'relu', 'kind': 'manual'}, {'api': 'relu6', 'op': 'relu6', 'kind': 'manual'}, {'api': 'rrelu', 'op': 'rrelu', 'kind': 'manual'}, {'api': 'selu', 'op': 'selu', 'kind': 'manual'}, {'api': 'sigmoid', 'op': 'sigmoid', 'kind': 'manual'}, {'api': 'silu', 'op': 'silu', 'kind': 'manual'}, {'api': 'softmin', 'op': 'softmin', 'kind': 'manual'}, {'api': 'softplus', 'op': 'softplus', 'kind': 'manual'}, {'api': 'softshrink', 'op': 'softshrink', 'kind': 'manual'}, {'api': 'softsign', 'op': 'softsign', 'kind': 'manual'}, {'api': 'tanh_shrink', 'op': 'tanh_shrink', 'kind': 'manual'}, {'api': 'thresholded_relu', 'op': 'thresholded_relu', 'kind': 'manual'}, {'api': 'affine_grid', 'op': 'affine_grid', 'kind': 'manual'}, {'api': 'bilinear', 'op': 'bilinear', 'kind': 'manual'}, {'api': 'channel_shuffle', 'op': 'channel_shuffle', 'kind': 'manual'}, {'api': 'cosine_similarity', 'op': 'cosine_similarity', 'kind': 'manual'}, {'api': 'dropout_infer', 'op': 'dropout_infer', 'kind': 'manual'}, {'api': 'embedding', 'op': 'embedding', 'kind': 'manual'}, {'api': 'fold', 'op': 'fold', 'kind': 'manual'}, {'api': 'grid_sample', 'op': 'grid_sample', 'kind': 'manual'}, {'api': 'interpolate', 'op': 'interpolate', 'kind': 'manual'}, {'api': 'label_smooth', 'op': 'label_smooth', 'kind': 'manual'}, {'api': 'one_hot_v2', 'op': 'one_hot_v2', 'kind': 'manual'}, {'api': 'pad3d', 'op': 'pad3d', 'kind': 'manual'}, {'api': 'pairwise_distance', 'op': 'pairwise_distance', 'kind': 'manual'}, {'api': 'pixel_shuffle', 'op': 'pixel_shuffle', 'kind': 'manual'}, {'api': 'pixel_unshuffle', 'op': 'pixel_unshuffle', 'kind': 'manual'}, {'api': 'temporal_shift', 'op': 'temporal_shift', 'kind': 'manual'}, {'api': 'unfold', 'op': 'unfold', 'kind': 'manual'}, {'api': 'flash_attn_unpadded', 'op': 'flash_attn_unpadded', 'kind': 'manual'}, {'api': 'bce_logits', 'op': 'bce_logits', 'kind': 'manual'}, {'api': 'bce_loss', 'op': 'bce_loss', 'kind': 'manual'}, {'api': 'cosine_embedding_loss', 'op': 'cosine_embedding_loss', 'kind': 'manual'}, {'api': 'ctc_loss', 'op': 'ctc_loss', 'kind': 'manual'}, {'api': 'dice_loss', 'op': 'dice_loss', 'kind': 'manual'}, {'api': 'gaussian_nll_loss', 'op': 'gaussian_nll_loss', 'kind': 'manual'}, {'api': 'hinge_embedding_loss', 'op': 'hinge_embedding_loss', 'kind': 'manual'}, {'api': 'huber_loss', 'op': 'huber_loss', 'kind': 'manual'}, {'api': 'kldiv_loss', 'op': 'kldiv_loss', 'kind': 'manual'}, {'api': 'l1_loss', 'op': 'l1_loss', 'kind': 'manual'}, {'api': 'log_loss', 'op': 'log_loss', 'kind': 'manual'}, {'api': 'margin_ranking_loss', 'op': 'margin_ranking_loss', 'kind': 'manual'}, {'api': 'mse_loss', 'op': 'mse_loss', 'kind': 'manual'}, {'api': 'multi_label_soft_margin_loss', 'op': 'multi_label_soft_margin_loss', 'kind': 'manual'}, {'api': 'nll_loss', 'op': 'nll_loss', 'kind': 'manual'}, {'api': 'npair_loss', 'op': 'npair_loss', 'kind': 'manual'}, {'api': 'poisson_nll_loss', 'op': 'poisson_nll_loss', 'kind': 'manual'}, {'api': 'sigmoid_focal_loss', 'op': 'sigmoid_focal_loss', 'kind': 'manual'}, {'api': 'smooth_l1', 'op': 'smooth_l1', 'kind': 'manual'}, {'api': 'soft_margin_loss', 'op': 'soft_margin_loss', 'kind': 'manual'}, {'api': 'square_error_cost', 'op': 'square_error_cost', 'kind': 'manual'}, {'api': 'triplet_margin_loss', 'op': 'triplet_margin_loss', 'kind': 'manual'}, {'api': 'batch_norm_infer', 'op': 'batch_norm_infer', 'kind': 'manual'}, {'api': 'lrn', 'op': 'lrn', 'kind': 'manual'}, {'api': 'normalize', 'op': 'normalize', 'kind': 'manual'}, {'api': 'spectral_norm', 'op': 'spectral_norm', 'kind': 'manual'}, {'api': 'max_unpool2d', 'op': 'max_unpool2d', 'kind': 'manual'}, {'api': 'gru_cell', 'op': 'gru_cell', 'kind': 'manual'}, {'api': 'lstm_cell', 'op': 'lstm_cell', 'kind': 'manual'}, {'api': 'rnn', 'op': 'rnn', 'kind': 'manual'}, {'api': 'rnn_cell', 'op': 'rnn_cell', 'kind': 'manual'}, {'api': 'weight_norm', 'op': 'weight_norm', 'kind': 'manual'}, {'api': 'allclose', 'op': 'allclose', 'kind': 'sig', 'differentiable': False, 'sig': 'x, y, rtol=1e-05, atol=1e-08, equal_nan=False', 'expr': 'dispatch(\n        "allclose",\n        lambda a, b, *, rtol, atol, equal_nan: jnp.allclose(\n            a, b, rtol=rtol, atol=atol, equal_nan=equal_nan),\n        (x, y), dict(rtol=float(rtol), atol=float(atol),\n                     equal_nan=bool(equal_nan)),\n        differentiable=False)\n'}, {'api': 'bitwise_not', 'op': 'bitwise_not', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("bitwise_not", jnp.bitwise_not, (x,), {},\n                    differentiable=False)\n'}, {'api': 'equal_all', 'op': 'equal_all', 'kind': 'sig', 'sig': 'x, y', 'prelude': 'def impl(a, b):\n    if a.shape != b.shape:\n        return jnp.asarray(False)\n    return jnp.all(a == b)\n', 'expr': 'dispatch("equal_all", impl, (x, y), {}, differentiable=False)\n'}, {'api': 'isclose', 'op': 'isclose', 'kind': 'sig', 'differentiable': False, 'sig': 'x, y, rtol=1e-05, atol=1e-08, equal_nan=False', 'expr': 'dispatch(\n        "isclose",\n        lambda a, b, *, rtol, atol, equal_nan: jnp.isclose(\n            a, b, rtol=rtol, atol=atol, equal_nan=equal_nan),\n        (x, y), dict(rtol=float(rtol), atol=float(atol),\n                     equal_nan=bool(equal_nan)),\n        differentiable=False)\n'}, {'api': 'isin', 'op': 'isin', 'kind': 'sig', 'differentiable': False, 'sig': 'x, test_x, assume_unique=False, invert=False', 'expr': 'dispatch(\n        "isin",\n        lambda a, b, *, invert, uniq: jnp.isin(\n            a, b, invert=invert, assume_unique=uniq),\n        (x, test_x), dict(invert=bool(invert),\n                          uniq=bool(assume_unique)),\n        differentiable=False)\n'}, {'api': 'logical_not', 'op': 'logical_not', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("logical_not", jnp.logical_not, (x,), {},\n                    differentiable=False)\n'}, {'api': 'arange', 'op': 'arange', 'kind': 'manual'}, {'api': 'assign', 'op': 'assign', 'kind': 'manual'}, {'api': 'bernoulli', 'op': 'bernoulli', 'kind': 'manual'}, {'api': 'clone', 'op': 'clone', 'kind': 'sig', 'sig': 'x', 'expr': 'dispatch("clone", lambda v: jnp.copy(v), (x,), {})\n'}, {'api': 'complex', 'op': 'complex', 'kind': 'sig', 'sig': 'real, imag', 'expr': 'dispatch("complex", lambda r, i: jax.lax.complex(r, i),\n                    (real, imag), {})\n'}, {'api': 'diag', 'op': 'diag', 'kind': 'manual'}, {'api': 'diag_embed', 'op': 'diag_embed', 'kind': 'manual'}, {'api': 'diagflat', 'op': 'diagflat', 'kind': 'sig', 'sig': 'x, offset=0', 'expr': 'dispatch("diagflat",\n                    lambda v, *, k: jnp.diagflat(v, k), (x,),\n                    dict(k=int(offset)))\n'}, {'api': 'eye', 'op': 'eye', 'kind': 'sig', 'sig': 'num_rows, num_columns=None, dtype=None', 'expr': 'dispatch(\n    "eye", lambda *, n, m, dtype: jnp.eye(n, m, dtype=dtype), (),\n    dict(n=int(num_rows),\n         m=None if num_columns is None else int(num_columns),\n         dtype=_jd(dtype)))\n'}, {'api': 'full', 'op': 'full', 'kind': 'manual'}, {'api': 'full_like', 'op': 'full_like', 'kind': 'sig', 'sig': 'x, fill_value, dtype=None', 'expr': 'dispatch(\n    "full_like",\n    lambda v, *, value, dtype: jnp.full_like(v, value, dtype), (x,),\n    dict(value=fill_value,\n         dtype=None if dtype is None else to_jax_dtype(dtype)),\n    differentiable=False)\n'}, {'api': 'gaussian', 'op': 'gaussian', 'kind': 'manual'}, {'api': 'gaussian_random', 'op': 'gaussian_random', 'kind': 'manual'}, {'api': 'linspace', 'op': 'linspace', 'kind': 'sig', 'sig': 'start, stop, num, dtype=None', 'prelude': 'start = start.item() if isinstance(start, Tensor) else start\nstop = stop.item() if isinstance(stop, Tensor) else stop\nnum = int(num.item()) if isinstance(num, Tensor) else int(num)\n', 'expr': 'dispatch(\n    "linspace",\n    lambda *, start, stop, num, dtype: jnp.linspace(\n        start, stop, num, dtype=dtype),\n    (), dict(start=start, stop=stop, num=num, dtype=_jd(dtype)))\n'}, {'api': 'logspace', 'op': 'logspace', 'kind': 'sig', 'sig': 'start, stop, num, base=10.0, dtype=None', 'expr': 'dispatch(\n    "logspace",\n    lambda *, start, stop, num, base, dtype: jnp.logspace(\n        start, stop, num, base=base, dtype=dtype),\n    (), dict(start=float(start), stop=float(stop), num=int(num),\n             base=float(base), dtype=_jd(dtype)))\n'}, {'api': 'meshgrid', 'op': 'meshgrid', 'kind': 'manual'}, {'api': 'multinomial', 'op': 'multinomial', 'kind': 'manual'}, {'api': 'ones', 'op': 'ones', 'kind': 'sig', 'sig': 'shape, dtype=None', 'expr': 'dispatch("ones", lambda *, shape, dtype: jnp.ones(shape, dtype),\n                (), dict(shape=_shape(shape), dtype=_jd(dtype)))\n'}, {'api': 'ones_like', 'op': 'ones_like', 'kind': 'like', 'impl': 'jnp.ones_like'}, {'api': 'poisson', 'op': 'poisson', 'kind': 'manual'}, {'api': 'randint', 'op': 'randint', 'kind': 'manual'}, {'api': 'randperm', 'op': 'randperm', 'kind': 'manual'}, {'api': 'tril', 'op': 'tril', 'kind': 'sig', 'sig': 'x, diagonal=0', 'expr': 'dispatch("tril", lambda v, *, k: jnp.tril(v, k), (x,),\n                    dict(k=int(diagonal)))\n'}, {'api': 'triu', 'op': 'triu', 'kind': 'sig', 'sig': 'x, diagonal=0', 'expr': 'dispatch("triu", lambda v, *, k: jnp.triu(v, k), (x,),\n                    dict(k=int(diagonal)))\n'}, {'api': 'uniform', 'op': 'uniform', 'kind': 'manual'}, {'api': 'uniform_random', 'op': 'uniform_random', 'kind': 'manual'}, {'api': 'zeros', 'op': 'zeros', 'kind': 'sig', 'sig': 'shape, dtype=None', 'expr': 'dispatch("zeros", lambda *, shape, dtype: jnp.zeros(shape, dtype),\n                (), dict(shape=_shape(shape), dtype=_jd(dtype)))\n'}, {'api': 'zeros_like', 'op': 'zeros_like', 'kind': 'like', 'impl': 'jnp.zeros_like'}, {'api': 'cholesky', 'op': 'cholesky', 'kind': 'sig', 'sig': 'x, upper=False', 'prelude': 'def impl(v, *, upper):\n    L = jnp.linalg.cholesky(v)\n    return jnp.swapaxes(L, -1, -2) if upper else L\n', 'expr': 'dispatch("cholesky", impl, (x,), dict(upper=bool(upper)))\n'}, {'api': 'cholesky_solve', 'op': 'cholesky_solve', 'kind': 'sig', 'sig': 'x, y, upper=False', 'prelude': 'def impl(b, L, *, upper):\n    return jax.scipy.linalg.cho_solve((L, not upper), b)\n', 'expr': 'dispatch("cholesky_solve", impl, (x, y), dict(upper=bool(upper)))\n'}, {'api': 'cond', 'op': 'cond', 'kind': 'manual'}, {'api': 'corrcoef', 'op': 'corrcoef', 'kind': 'sig', 'sig': 'x, rowvar=True', 'expr': 'dispatch("corrcoef",\n                    lambda v, *, rowvar: jnp.corrcoef(v, rowvar=rowvar),\n                    (x,), dict(rowvar=bool(rowvar)))\n'}, {'api': 'cov', 'op': 'cov', 'kind': 'sig', 'sig': 'x, rowvar=True, ddof=True, fweights=None, aweights=None', 'prelude': 'ts = (x,) + tuple(w for w in (fweights, aweights) if w is not None)\n', 'expr': 'dispatch(\n        "cov",\n        lambda v, *ws, rowvar, ddof, has_f, has_a: jnp.cov(\n            v, rowvar=rowvar, ddof=1 if ddof else 0,\n            fweights=ws[0] if has_f else None,\n            aweights=ws[-1] if has_a else None),\n        ts, dict(rowvar=bool(rowvar), ddof=bool(ddof),\n                 has_f=fweights is not None,\n                 has_a=aweights is not None))\n'}, {'api': 'cross', 'op': 'cross', 'kind': 'manual'}, {'api': 'determinant', 'op': 'determinant', 'kind': 'manual'}, {'api': 'dot', 'op': 'dot', 'kind': 'sig', 'sig': 'x, y', 'prelude': 'def impl(a, b):\n    return jnp.sum(a * b, axis=-1)\n', 'expr': 'dispatch("dot", impl, (x, y), {})\n'}, {'api': 'eigh', 'op': 'eigh', 'kind': 'sig', 'sig': 'x, UPLO="L"', 'prelude': 'def impl(v, *, uplo):\n    return tuple(jnp.linalg.eigh(v, symmetrize_input=True))\n', 'expr': 'dispatch("eigh", impl, (x,), dict(uplo=UPLO))\n'}, {'api': 'eigvalsh', 'op': 'eigvalsh', 'kind': 'sig', 'sig': 'x, UPLO="L"', 'expr': 'dispatch("eigvalsh",\n                    lambda v, *, uplo: jnp.linalg.eigvalsh(\n                        v, UPLO=uplo), (x,),\n                    dict(uplo=UPLO))\n'}, {'api': 'householder_product', 'op': 'householder_product', 'kind': 'manual'}, {'api': 'inverse', 'op': 'inverse', 'kind': 'manual'}, {'api': 'lstsq', 'op': 'lstsq', 'kind': 'sig', 'sig': 'x, y, rcond=None, driver=None', 'prelude': 'def impl(a, b, *, rcond):\n    sol, res, rank, sv = jnp.linalg.lstsq(a, b, rcond=rcond)\n    return sol, res, rank.astype(jnp.int64), sv\n', 'expr': 'dispatch("lstsq", impl, (x, y), dict(rcond=rcond))\n'}, {'api': 'lu', 'op': 'lu', 'kind': 'manual'}, {'api': 'lu_unpack', 'op': 'lu_unpack', 'kind': 'manual'}, {'api': 'matrix_exp', 'op': 'matrix_exp', 'kind': 'sig', 'sig': 'x', 'expr': 'dispatch("matrix_exp", jax.scipy.linalg.expm, (x,), {})\n'}, {'api': 'matrix_power', 'op': 'matrix_power', 'kind': 'sig', 'sig': 'x, n', 'expr': 'dispatch("matrix_power",\n                    lambda v, *, n: jnp.linalg.matrix_power(v, n), (x,),\n                    dict(n=int(n)))\n'}, {'api': 'matrix_rank', 'op': 'matrix_rank', 'kind': 'sig', 'sig': 'x, tol=None, hermitian=False', 'prelude': 'def impl(v, *, tol):\n    return jnp.linalg.matrix_rank(v, tol=tol).astype(jnp.int64)\nt_ = tol.item() if isinstance(tol, Tensor) else tol\n', 'expr': 'dispatch("matrix_rank", impl, (x,), dict(tol=t_),\n                differentiable=False)\n'}, {'api': 'multi_dot', 'op': 'multi_dot', 'kind': 'sig', 'sig': 'tensors', 'expr': 'dispatch("multi_dot", lambda *vs: jnp.linalg.multi_dot(vs),\n                    tuple(tensors), {})\n'}, {'api': 'pca_lowrank', 'op': 'pca_lowrank', 'kind': 'manual'}, {'api': 'stft', 'op': 'stft', 'kind': 'manual'}, {'api': 'vector_norm', 'op': 'vector_norm', 'kind': 'manual'}, {'api': 'matrix_norm', 'op': 'matrix_norm', 'kind': 'manual'}, {'api': 'svdvals', 'op': 'svdvals', 'kind': 'manual'}, {'api': 'ormqr', 'op': 'ormqr', 'kind': 'manual'}, {'api': 'as_complex', 'op': 'as_complex', 'kind': 'manual'}, {'api': 'as_real', 'op': 'as_real', 'kind': 'manual'}, {'api': 'istft', 'op': 'istft', 'kind': 'manual'}, {'api': 'pinv', 'op': 'pinv', 'kind': 'sig', 'sig': 'x, rcond=1e-15, hermitian=False', 'expr': 'dispatch("pinv",\n                    lambda v, *, rcond, hermitian: jnp.linalg.pinv(\n                        v, rcond=rcond, hermitian=hermitian),\n                    (x,), dict(rcond=float(rcond) if not isinstance(\n                        rcond, Tensor) else float(rcond.item()),\n                        hermitian=bool(hermitian)))\n'}, {'api': 'qr', 'op': 'qr', 'kind': 'manual'}, {'api': 'slogdeterminant', 'op': 'slogdeterminant', 'kind': 'manual'}, {'api': 'solve', 'op': 'solve', 'kind': 'sig', 'sig': 'x, y', 'prelude': 'def impl(a, b):\n    if b.ndim == a.ndim - 1:\n        return jnp.linalg.solve(a, b[..., None])[..., 0]\n    return jnp.linalg.solve(a, b)\n', 'expr': 'dispatch("solve", impl, (x, y), {})\n'}, {'api': 'svd', 'op': 'svd', 'kind': 'sig', 'sig': 'x, full_matrices=False', 'prelude': 'def impl(v, *, fm):\n    return tuple(jnp.linalg.svd(v, full_matrices=fm))\n', 'expr': 'dispatch("svd", impl, (x,), dict(fm=bool(full_matrices)))\n'}, {'api': 'transpose2', 'op': 'transpose2', 'kind': 'manual'}, {'api': 'triangular_solve', 'op': 'triangular_solve', 'kind': 'sig', 'sig': 'x, y, upper=True, transpose=False, unitriangular=False', 'prelude': 'def impl(a, b, *, upper, trans, unit):\n    return jax.scipy.linalg.solve_triangular(\n        a, b, lower=not upper, trans=1 if trans else 0,\n        unit_diagonal=unit)\n', 'expr': 'dispatch("triangular_solve", impl, (x, y),\n                dict(upper=bool(upper), trans=bool(transpose),\n                     unit=bool(unitriangular)))\n'}, {'api': 'vander', 'op': 'vander', 'kind': 'sig', 'sig': 'x, n=None, increasing=False', 'expr': 'dispatch(\n        "vander",\n        lambda v, *, n, inc: jnp.vander(v, n, increasing=inc), (x,),\n        dict(n=None if n is None else int(n), inc=bool(increasing)))\n'}, {'api': 'vecdot', 'op': 'vecdot', 'kind': 'sig', 'sig': 'x, y, axis=-1', 'expr': 'dispatch("vecdot",\n                    lambda a, b, *, axis: jnp.sum(a * b, axis=axis),\n                    (x, y), dict(axis=int(axis)))\n'}, {'api': 'argsort', 'op': 'argsort', 'kind': 'sig', 'sig': 'x, axis=-1, descending=False, stable=False', 'prelude': 'def impl(v, *, axis, desc):\n    out = jnp.argsort(v, axis=axis, stable=True)\n    return (jnp.flip(out, axis) if desc else out).astype(jnp.int64)\n', 'expr': 'dispatch("argsort", impl, (x,),\n                dict(axis=int(axis), desc=bool(descending)),\n                differentiable=False)\n'}, {'api': 'broadcast_tensors', 'op': 'broadcast_tensors', 'kind': 'manual'}, {'api': 'broadcast_to', 'op': 'broadcast_to', 'kind': 'sig', 'sig': 'x, shape', 'expr': 'dispatch("broadcast_to",\n                lambda v, *, shape: jnp.broadcast_to(v, shape), (x,),\n                dict(shape=tuple(_int_list(shape))))\n'}, {'api': 'cast', 'op': 'cast', 'kind': 'sig', 'sig': 'x, dtype', 'prelude': 'jd = to_jax_dtype(dtype)\n', 'expr': 'dispatch("cast", lambda v, *, dtype: jnp.asarray(v, dtype), (x,),\n                dict(dtype=jd))\n'}, {'api': 'column_stack', 'op': 'column_stack', 'kind': 'sig', 'sig': 'x', 'prelude': 'def impl(*vs):\n    return jnp.column_stack(vs)\n', 'expr': 'dispatch("column_stack", impl, tuple(x), {})\n'}, {'api': 'concat', 'op': 'concat', 'kind': 'sig', 'sig': 'x, axis=0', 'prelude': 'axis = int(axis.item()) if isinstance(axis, Tensor) else int(axis)\nxs = builtins.list(x)\n', 'expr': 'dispatch("concat",\n                lambda *vs, axis: jnp.concatenate(vs, axis), tuple(xs),\n                dict(axis=axis))\n'}, {'api': 'diagonal', 'op': 'diagonal', 'kind': 'sig', 'sig': 'x, offset=0, axis1=0, axis2=1', 'expr': 'dispatch(\n        "diagonal",\n        lambda v, offset, axis1, axis2: jnp.diagonal(\n            v, offset=offset, axis1=axis1, axis2=axis2),\n        (x,), dict(offset=int(offset), axis1=int(axis1),\n                   axis2=int(axis2)))\n'}, {'api': 'expand', 'op': 'expand', 'kind': 'manual'}, {'api': 'flatten', 'op': 'flatten', 'kind': 'sig', 'sig': 'x, start_axis=0, stop_axis=-1', 'prelude': 'def impl(v, *, s, e):\n    nd = v.ndim\n    s_, e_ = s % nd if nd else 0, e % nd if nd else 0\n    new_shape = v.shape[:s_] + (-1,) + v.shape[e_ + 1:]\n    return jnp.reshape(v, new_shape)\n', 'expr': 'dispatch("flatten", impl, (x,),\n                dict(s=int(start_axis), e=int(stop_axis)))\n'}, {'api': 'flip', 'op': 'flip', 'kind': 'sig', 'sig': 'x, axis', 'expr': 'dispatch("flip", lambda v, *, axis: jnp.flip(v, axis), (x,),\n                dict(axis=tuple(_int_list(axis))))\n'}, {'api': 'gather', 'op': 'gather', 'kind': 'sig', 'sig': 'x, index, axis=0', 'prelude': 'axis = int(axis.item()) if isinstance(axis, Tensor) else int(axis)\ndef impl(v, idx, *, axis):\n    return jnp.take(v, idx.reshape(-1) if idx.ndim > 1 else idx,\n                    axis=axis)\n', 'expr': 'dispatch("gather", impl, (x, index), dict(axis=axis))\n'}, {'api': 'gather_nd', 'op': 'gather_nd', 'kind': 'sig', 'sig': 'x, index', 'prelude': 'def impl(v, idx):\n    return v[tuple(jnp.moveaxis(idx, -1, 0))]\n', 'expr': 'dispatch("gather_nd", impl, (x, index), {})\n'}, {'api': 'index_add', 'op': 'index_add', 'kind': 'sig', 'sig': 'x, index, axis, value', 'prelude': 'def impl(v, idx, val, *, axis):\n    vm = jnp.moveaxis(v, axis, 0)\n    valm = jnp.moveaxis(val, axis, 0)\n    out = vm.at[idx].add(valm)\n    return jnp.moveaxis(out, 0, axis)\n', 'expr': 'dispatch("index_add", impl, (x, index, value),\n                dict(axis=int(axis)))\n'}, {'api': 'index_fill', 'op': 'index_fill', 'kind': 'sig', 'sig': 'x, index, axis, value', 'prelude': 'def impl(v, idx, axis, value):\n    moved = jnp.moveaxis(v, axis, 0)\n    moved = moved.at[idx].set(value)\n    return jnp.moveaxis(moved, 0, axis)\n', 'expr': 'dispatch("index_fill", impl, (x, index),\n                dict(axis=int(axis),\n                     value=float(value) if not isinstance(\n                         value, (list, tuple)) else value))\n'}, {'api': 'index_put', 'op': 'index_put', 'kind': 'sig', 'sig': 'x, indices, value, accumulate=False', 'prelude': 'def impl(v, val, *idx, accumulate):\n    if accumulate:\n        return v.at[tuple(idx)].add(val)\n    return v.at[tuple(idx)].set(val)\n', 'expr': 'dispatch("index_put", impl, (x, value) + tuple(indices),\n                dict(accumulate=bool(accumulate)))\n'}, {'api': 'index_sample', 'op': 'index_sample', 'kind': 'sig', 'sig': 'x, index', 'prelude': 'def impl(v, idx):\n    return jnp.take_along_axis(v, idx, axis=1)\n', 'expr': 'dispatch("index_sample", impl, (x, index), {})\n'}, {'api': 'index_select', 'op': 'index_select', 'kind': 'sig', 'sig': 'x, index, axis=0', 'prelude': 'def impl(v, idx, *, axis):\n    return jnp.take(v, idx, axis=axis)\n', 'expr': 'dispatch("index_select", impl, (x, index), dict(axis=int(axis)))\n'}, {'api': 'masked_fill', 'op': 'masked_fill', 'kind': 'sig', 'sig': 'x, mask, value', 'prelude': 'def impl(v, m, *, value):\n    return jnp.where(m, jnp.asarray(value, v.dtype), v)\nvalue = value.item() if isinstance(value, Tensor) else value\n', 'expr': 'dispatch("masked_fill", impl, (x, mask), dict(value=value))\n'}, {'api': 'masked_scatter', 'op': 'masked_scatter', 'kind': 'manual'}, {'api': 'moveaxis', 'op': 'moveaxis', 'kind': 'sig', 'sig': 'x, source, destination', 'expr': 'dispatch(\n    "moveaxis",\n    lambda v, *, s, d: jnp.moveaxis(v, s, d), (x,),\n    dict(s=tuple(_int_list(source)), d=tuple(_int_list(destination))))\n'}, {'api': 'put_along_axis', 'op': 'put_along_axis', 'kind': 'manual'}, {'api': 'repeat_interleave', 'op': 'repeat_interleave', 'kind': 'manual'}, {'api': 'reshape', 'op': 'reshape', 'kind': 'sig', 'sig': 'x, shape', 'prelude': 'shape = tuple(_int_list(shape))\n', 'expr': 'dispatch("reshape", lambda v, *, shape: jnp.reshape(v, shape),\n                (x,), dict(shape=shape))\n'}, {'api': 'roll', 'op': 'roll', 'kind': 'sig', 'sig': 'x, shifts, axis=None', 'expr': 'dispatch(\n    "roll", lambda v, *, shifts, axis: jnp.roll(v, shifts, axis), (x,),\n    dict(shifts=tuple(_int_list(shifts)) if not isinstance(shifts, int)\n         else int(shifts),\n         axis=None if axis is None else (\n             tuple(_int_list(axis)) if not isinstance(axis, int)\n             else int(axis))))\n'}, {'api': 'rot90', 'op': 'rot90', 'kind': 'sig', 'sig': 'x, k=1, axes=(0, 1)', 'expr': 'dispatch("rot90", lambda v, *, k, axes: jnp.rot90(v, k, axes),\n                    (x,), dict(k=int(k), axes=tuple(axes)))\n'}, {'api': 'row_stack', 'op': 'row_stack', 'kind': 'sig', 'sig': 'x', 'prelude': 'def impl(*vs):\n    return jnp.vstack(vs)\n', 'expr': 'dispatch("row_stack", impl, tuple(x), {})\n'}, {'api': 'scatter', 'op': 'scatter', 'kind': 'sig', 'sig': 'x, index, updates, overwrite=True', 'prelude': 'def impl(v, idx, upd, *, overwrite):\n    idx = idx.reshape(-1)\n    if overwrite:\n        return v.at[idx].set(upd)\n    base = v.at[idx].set(jnp.zeros_like(upd))\n    return base.at[idx].add(upd)\n', 'expr': 'dispatch("scatter", impl, (x, index, updates),\n                dict(overwrite=bool(overwrite)))\n'}, {'api': 'scatter_nd', 'op': 'scatter_nd', 'kind': 'sig', 'sig': 'index, updates, shape', 'prelude': 'def impl(idx, upd, *, shape):\n    z = jnp.zeros(shape, upd.dtype)\n    return z.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)\n', 'expr': 'dispatch("scatter_nd", impl, (index, updates),\n                dict(shape=tuple(_int_list(shape))))\n'}, {'api': 'scatter_nd_add', 'op': 'scatter_nd_add', 'kind': 'sig', 'sig': 'x, index, updates', 'prelude': 'def impl(v, idx, upd):\n    return v.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd)\n', 'expr': 'dispatch("scatter_nd_add", impl, (x, index, updates), {})\n'}, {'api': 'searchsorted', 'op': 'searchsorted', 'kind': 'manual'}, {'api': 'select_scatter', 'op': 'select_scatter', 'kind': 'sig', 'sig': 'x, values, axis, index', 'prelude': 'def impl(v, src, axis, index):\n    idx = [builtins.slice(None)] * v.ndim  # `slice` op shadows builtin\n    idx[axis] = index\n    return v.at[tuple(idx)].set(src)\n', 'expr': 'dispatch("select_scatter", impl, (x, values),\n                dict(axis=int(axis), index=int(index)))\n'}, {'api': 'set_value', 'op': 'set_value', 'kind': 'manual'}, {'api': 'shard_index', 'op': 'shard_index', 'kind': 'sig', 'sig': 'input, index_num, nshards, shard_id, ignore_value=-1', 'prelude': 'def impl(v, *, index_num, nshards, shard_id, ignore_value):\n    size = index_num // nshards\n    lo, hi = shard_id * size, (shard_id + 1) * size\n    ok = (v >= lo) & (v < hi)\n    return jnp.where(ok, v - lo, ignore_value)\n', 'expr': 'dispatch("shard_index", impl, (input,),\n                dict(index_num=int(index_num), nshards=int(nshards),\n                     shard_id=int(shard_id),\n                     ignore_value=int(ignore_value)),\n                differentiable=False)\n'}, {'api': 'slice', 'op': 'slice', 'kind': 'manual'}, {'api': 'slice_scatter', 'op': 'slice_scatter', 'kind': 'manual'}, {'api': 'sort', 'op': 'sort', 'kind': 'sig', 'sig': 'x, axis=-1, descending=False, stable=False', 'prelude': 'def impl(v, *, axis, desc):\n    out = jnp.sort(v, axis=axis)\n    return jnp.flip(out, axis) if desc else out\n', 'expr': 'dispatch("sort", impl, (x,),\n                dict(axis=int(axis), desc=bool(descending)))\n'}, {'api': 'split', 'op': 'split', 'kind': 'manual'}, {'api': 'squeeze', 'op': 'squeeze', 'kind': 'manual'}, {'api': 'stack', 'op': 'stack', 'kind': 'sig', 'sig': 'x, axis=0', 'prelude': 'xs = builtins.list(x)\n', 'expr': 'dispatch("stack", lambda *vs, axis: jnp.stack(vs, axis),\n                tuple(xs), dict(axis=int(axis)))\n'}, {'api': 'swapaxes', 'op': 'swapaxes', 'kind': 'sig', 'sig': 'x, axis1, axis2', 'expr': 'dispatch("swapaxes",\n                    lambda v, *, a, b: jnp.swapaxes(v, a, b), (x,),\n                    dict(a=int(axis1), b=int(axis2)))\n'}, {'api': 'take_along_axis', 'op': 'take_along_axis', 'kind': 'sig', 'sig': 'arr, indices, axis, broadcast=True', 'prelude': 'def impl(v, idx, *, axis):\n    return jnp.take_along_axis(v, idx, axis=axis)\n', 'expr': 'dispatch("take_along_axis", impl, (arr, indices),\n                dict(axis=int(axis)))\n'}, {'api': 'tensordot', 'op': 'tensordot', 'kind': 'manual'}, {'api': 'tile', 'op': 'tile', 'kind': 'sig', 'sig': 'x, repeat_times', 'expr': 'dispatch("tile", lambda v, *, reps: jnp.tile(v, reps), (x,),\n                dict(reps=tuple(_int_list(repeat_times))))\n'}, {'api': 'top_k_v2', 'op': 'top_k_v2', 'kind': 'manual'}, {'api': 'transpose', 'op': 'transpose', 'kind': 'sig', 'sig': 'x, perm', 'prelude': 'perm = tuple(_int_list(perm))\n', 'expr': 'dispatch("transpose", lambda v, *, perm: jnp.transpose(v, perm),\n                (x,), dict(perm=perm))\n'}, {'api': 'unbind', 'op': 'unbind', 'kind': 'manual'}, {'api': 'unsqueeze', 'op': 'unsqueeze', 'kind': 'sig', 'sig': 'x, axis', 'prelude': 'axes = tuple(_int_list(axis))\n', 'expr': 'dispatch("unsqueeze",\n                lambda v, *, axes: jnp.expand_dims(v, axes), (x,),\n                dict(axes=axes))\n'}, {'api': 'where', 'op': 'where', 'kind': 'manual'}, {'api': 'clip', 'op': 'clip', 'kind': 'sig', 'sig': 'x, min=None, max=None', 'prelude': 'min = min.item() if isinstance(min, Tensor) and min.size == 1 else min\nmax = max.item() if isinstance(max, Tensor) and max.size == 1 else max\n', 'expr': 'dispatch("clip", lambda v, *, lo, hi: jnp.clip(v, lo, hi), (x,),\n                dict(lo=min, hi=max))\n'}, {'api': 'copysign', 'op': 'copysign', 'kind': 'sig', 'sig': 'x, y', 'expr': 'dispatch("copysign", lambda a, b: jnp.copysign(a, b), (x, y),\n                    {})\n'}, {'api': 'cummax', 'op': 'cummax', 'kind': 'manual'}, {'api': 'cummin', 'op': 'cummin', 'kind': 'manual'}, {'api': 'cumprod', 'op': 'cumprod', 'kind': 'cum', 'impl': 'jnp.cumprod', 'axis_arg': 'dim'}, {'api': 'cumsum', 'op': 'cumsum', 'kind': 'cum', 'impl': 'jnp.cumsum'}, {'api': 'cumulative_trapezoid', 'op': 'cumulative_trapezoid', 'kind': 'manual'}, {'api': 'diff', 'op': 'diff', 'kind': 'manual'}, {'api': 'frexp', 'op': 'frexp', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'prelude': 'def impl(v):\n    m, e = jnp.frexp(v)\n    return m, e.astype(jnp.int32)\n', 'expr': 'dispatch("frexp", impl, (x,), {}, differentiable=False)\n'}, {'api': 'gammaln', 'op': 'gammaln', 'kind': 'sig', 'sig': 'x', 'expr': 'dispatch("gammaln",\n                    lambda v: jax.scipy.special.gammaln(v), (x,), {})\n'}, {'api': 'i0', 'op': 'i0', 'kind': 'sig', 'sig': 'x', 'expr': 'dispatch("i0", lambda v: jax.scipy.special.i0(v), (x,), {})\n'}, {'api': 'i1', 'op': 'i1', 'kind': 'sig', 'sig': 'x', 'expr': 'dispatch("i1", lambda v: jax.scipy.special.i1(v), (x,), {})\n'}, {'api': 'increment', 'op': 'increment', 'kind': 'manual'}, {'api': 'isfinite', 'op': 'isfinite', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("isfinite", jnp.isfinite, (x,), {}, differentiable=False)\n'}, {'api': 'isinf', 'op': 'isinf', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("isinf", jnp.isinf, (x,), {}, differentiable=False)\n'}, {'api': 'isnan', 'op': 'isnan', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("isnan", jnp.isnan, (x,), {}, differentiable=False)\n'}, {'api': 'isneginf', 'op': 'isneginf', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("isneginf", lambda v: jnp.isneginf(v), (x,), {},\n                    differentiable=False)\n'}, {'api': 'isposinf', 'op': 'isposinf', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("isposinf", lambda v: jnp.isposinf(v), (x,), {},\n                    differentiable=False)\n'}, {'api': 'isreal', 'op': 'isreal', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("isreal", lambda v: jnp.isreal(v), (x,), {},\n                    differentiable=False)\n'}, {'api': 'kron', 'op': 'kron', 'kind': 'sig', 'sig': 'x, y', 'expr': 'dispatch("kron", jnp.kron, (x, y), {})\n'}, {'api': 'lerp', 'op': 'lerp', 'kind': 'sig', 'sig': 'x, y, weight', 'expr': 'dispatch("lerp", lambda a, b, w: a + w * (b - a), (x, y, weight),\n                    {})\n'}, {'api': 'logcumsumexp', 'op': 'logcumsumexp', 'kind': 'sig', 'sig': 'x, axis=None, dtype=None', 'prelude': 'def impl(v, axis, dtype):\n    if dtype is not None:\n        v = v.astype(dtype)\n    if axis is None:\n        v, axis = v.reshape(-1), 0\n    # global-max stabilization: exact in log domain, one pass\n    mx = jnp.max(v, axis=axis, keepdims=True)\n    return jnp.log(jnp.cumsum(jnp.exp(v - mx), axis=axis)) + mx\n', 'expr': 'dispatch("logcumsumexp", impl, (x,),\n                dict(axis=axis, dtype=None if dtype is None\n                     else to_jax_dtype(dtype)))\n'}, {'api': 'logit', 'op': 'logit', 'kind': 'sig', 'sig': 'x, eps=None', 'prelude': 'def impl(v, *, eps):\n    if eps is not None:\n        v = jnp.clip(v, eps, 1.0 - eps)\n    return jnp.log(v) - jnp.log1p(-v)\n', 'expr': 'dispatch("logit", impl, (x,), dict(eps=eps))\n'}, {'api': 'nan_to_num', 'op': 'nan_to_num', 'kind': 'sig', 'sig': 'x, nan=0.0, posinf=None, neginf=None', 'expr': 'dispatch(\n        "nan_to_num",\n        lambda v, *, nan, posinf, neginf: jnp.nan_to_num(\n            v, nan=nan, posinf=posinf, neginf=neginf),\n        (x,), dict(nan=nan, posinf=posinf, neginf=neginf))\n'}, {'api': 'nextafter', 'op': 'nextafter', 'kind': 'sig', 'differentiable': False, 'sig': 'x, y', 'expr': 'dispatch("nextafter", jnp.nextafter, (x, y), {},\n                    differentiable=False)\n'}, {'api': 'outer', 'op': 'outer', 'kind': 'sig', 'sig': 'x, y', 'expr': 'dispatch("outer", lambda a, b: jnp.outer(a, b), (x, y), {})\n'}, {'api': 'polar', 'op': 'polar', 'kind': 'sig', 'sig': 'abs, angle', 'expr': 'dispatch(\n        "polar",\n        lambda r, t: jax.lax.complex(r * jnp.cos(t), r * jnp.sin(t)),\n        (abs, angle), {})\n'}, {'api': 'polygamma', 'op': 'polygamma', 'kind': 'sig', 'sig': 'x, n', 'expr': 'dispatch("polygamma",\n                    lambda v, n: jax.scipy.special.polygamma(n, v),\n                    (x,), dict(n=int(n)))\n'}, {'api': 'renorm', 'op': 'renorm', 'kind': 'manual'}, {'api': 'scale', 'op': 'scale', 'kind': 'sig', 'sig': 'x, scale=1.0, bias=0.0, bias_after_scale=True, act=None', 'prelude': 'def impl(v, s, *, bias, after):\n    s = jnp.asarray(s, v.dtype)\n    return v * s + bias if after else (v + bias) * s\ns = scale if isinstance(scale, Tensor) else float(scale)\n', 'expr': 'dispatch("scale", impl, (x, s),\n                dict(bias=float(bias), after=bool(bias_after_scale)))\n'}, {'api': 'signbit', 'op': 'signbit', 'kind': 'sig', 'differentiable': False, 'sig': 'x', 'expr': 'dispatch("signbit", lambda v: jnp.signbit(v), (x,), {},\n                    differentiable=False)\n'}, {'api': 'sinc', 'op': 'sinc', 'kind': 'sig', 'sig': 'x', 'expr': 'dispatch("sinc", lambda v: jnp.sinc(v), (x,), {})\n'}, {'api': 'take', 'op': 'take', 'kind': 'sig', 'sig': 'x, index, mode="raise"', 'prelude': 'def impl(v, idx, *, mode):\n    flat = v.reshape(-1)\n    if mode == "wrap":\n        idx = jnp.mod(idx, flat.shape[0])\n    elif mode == "clip":\n        idx = jnp.clip(idx, 0, flat.shape[0] - 1)\n    return flat[idx]\n', 'expr': 'dispatch("take", impl, (x, index), dict(mode=mode))\n'}, {'api': 'trace', 'op': 'trace', 'kind': 'sig', 'sig': 'x, offset=0, axis1=0, axis2=1', 'expr': 'dispatch(\n        "trace",\n        lambda v, *, k, a1, a2: jnp.trace(v, k, a1, a2), (x,),\n        dict(k=int(offset), a1=int(axis1), a2=int(axis2)))\n'}, {'api': 'trapezoid', 'op': 'trapezoid', 'kind': 'sig', 'sig': 'y, x=None, dx=None, axis=-1', 'prelude': 'def impl(yv, *maybe_x, dx, axis):\n    xv = maybe_x[0] if maybe_x else None\n    return jnp.trapezoid(yv, x=xv, dx=1.0 if dx is None else dx,\n                         axis=axis)\nargs = (y, x) if x is not None else (y,)\n', 'expr': 'dispatch("trapezoid", impl, args, dict(dx=dx, axis=axis))\n'}, {'api': 'arg_max', 'op': 'arg_max', 'kind': 'manual'}, {'api': 'arg_min', 'op': 'arg_min', 'kind': 'manual'}, {'api': 'count_nonzero', 'op': 'count_nonzero', 'kind': 'reduction', 'impl': 'jnp.count_nonzero', 'differentiable': False, 'post': '.astype(jnp.int64)'}, {'api': 'kthvalue', 'op': 'kthvalue', 'kind': 'sig', 'sig': 'x, k, axis=-1, keepdim=False', 'prelude': 'def impl(v, *, k, axis, keepdims):\n    srt = jnp.sort(v, axis=axis)\n    idxs = jnp.argsort(v, axis=axis, stable=True)\n    val = jnp.take(srt, k - 1, axis=axis)\n    idx = jnp.take(idxs, k - 1, axis=axis)\n    if keepdims:\n        val = jnp.expand_dims(val, axis)\n        idx = jnp.expand_dims(idx, axis)\n    return val, idx.astype(jnp.int64)\n', 'expr': 'dispatch("kthvalue", impl, (x,),\n                dict(k=int(k), axis=int(axis), keepdims=bool(keepdim)))\n'}, {'api': 'median', 'op': 'median', 'kind': 'sig', 'sig': 'x, axis=None, keepdim=False, mode="avg"', 'prelude': 'def impl(v, *, axis, keepdims, mode):\n    if mode == "avg":\n        return jnp.median(v, axis=axis, keepdims=keepdims)\n    # \'min\' mode: lower of the two middle values + its index\n    if axis is None:\n        vf = v.reshape(-1)\n        ax = 0\n    else:\n        vf, ax = v, axis\n    n = vf.shape[ax]\n    k = (n - 1) // 2\n    srt = jnp.sort(vf, axis=ax)\n    val = jnp.take(srt, k, axis=ax)\n    if keepdims:\n        val = jnp.expand_dims(val, ax if axis is not None else ())\n    return val\n', 'expr': 'dispatch("median", impl, (x,),\n                dict(axis=None if axis is None else int(axis),\n                     keepdims=bool(keepdim), mode=mode))\n'}, {'api': 'nanmean', 'op': 'nanmean', 'kind': 'reduction', 'impl': 'jnp.nanmean'}, {'api': 'nanmedian', 'op': 'nanmedian', 'kind': 'sig', 'sig': 'x, axis=None, keepdim=False, mode="avg"', 'expr': 'dispatch(\n        "nanmedian",\n        lambda v, *, axis, keepdims: jnp.nanmedian(v, axis=axis,\n                                                   keepdims=keepdims),\n        (x,), dict(axis=_axis(axis), keepdims=bool(keepdim)))\n'}, {'api': 'nanquantile', 'op': 'nanquantile', 'kind': 'sig', 'sig': 'x, q, axis=None, keepdim=False, interpolation="linear"', 'prelude': 'qv = q if not isinstance(q, Tensor) else q.numpy()\n', 'expr': 'dispatch(\n    "nanquantile",\n    lambda v, *, q, axis, keepdims, method: jnp.nanquantile(\n        v.astype(jnp.float32), jnp.asarray(q), axis=axis,\n        keepdims=keepdims, method=method),\n    (x,), dict(q=qv, axis=_axis(axis), keepdims=bool(keepdim),\n               method=interpolation))\n'}, {'api': 'nansum', 'op': 'nansum', 'kind': 'reduction', 'impl': 'jnp.nansum', 'dtype': True}, {'api': 'quantile', 'op': 'quantile', 'kind': 'manual'}, {'api': 'all', 'op': 'reduce_all', 'kind': 'reduction', 'impl': 'jnp.all', 'differentiable': False}, {'api': 'any', 'op': 'reduce_any', 'kind': 'reduction', 'impl': 'jnp.any', 'differentiable': False}, {'api': 'max', 'op': 'reduce_max', 'kind': 'reduction', 'impl': 'jnp.max'}, {'api': 'min', 'op': 'reduce_min', 'kind': 'reduction', 'impl': 'jnp.min'}, {'api': 'adadelta', 'op': 'adadelta', 'kind': 'manual'}, {'api': 'adagrad', 'op': 'adagrad', 'kind': 'manual'}, {'api': 'adamax', 'op': 'adamax', 'kind': 'manual'}, {'api': 'adamw', 'op': 'adamw', 'kind': 'manual'}, {'api': 'lamb', 'op': 'lamb', 'kind': 'manual'}, {'api': 'momentum', 'op': 'momentum', 'kind': 'manual'}, {'api': 'rmsprop', 'op': 'rmsprop', 'kind': 'manual'}, {'api': 'sgd', 'op': 'sgd', 'kind': 'manual'}, {'api': 'fake_quantize_dequantize', 'op': 'fake_quantize_dequantize', 'kind': 'manual'}, {'api': 'switch_case', 'op': 'switch_case', 'kind': 'manual'}, {'api': 'while_loop', 'op': 'while_loop', 'kind': 'manual'}, {'api': 'box_coder', 'op': 'box_coder', 'kind': 'manual'}, {'api': 'box_iou', 'op': 'box_iou', 'kind': 'manual'}, {'api': 'deform_conv2d', 'op': 'deform_conv2d', 'kind': 'manual'}, {'api': 'roi_align', 'op': 'roi_align', 'kind': 'manual'}, {'api': 'roi_pool', 'op': 'roi_pool', 'kind': 'manual'}, {'api': 'yolo_box', 'op': 'yolo_box', 'kind': 'manual'}, {'api': 'scan_layer_stack', 'op': 'scan_layer_stack', 'kind': 'manual'}, {'api': 'max_unpool3d', 'op': 'max_unpool3d', 'kind': 'manual'}, {'api': 'multi_margin_loss', 'op': 'multi_margin_loss', 'kind': 'manual', 'amp': 'black'}, {'api': 'hsigmoid_loss', 'op': 'hsigmoid_loss', 'kind': 'manual', 'amp': 'black'}]


OP_TABLE = {r["op"]: dict(api=r["api"], kind=r["kind"],
                          amp=r.get("amp", "none"),
                          vjp=r.get("vjp", "jax"),
                          differentiable=r.get("differentiable", True))
            for r in __SCHEMA__}
