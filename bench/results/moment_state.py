"""A moment state fetched to the host, as the caller reads it, compared
with the reference's count, mean and variance per channel: the count
exactly, the mean in units of the reference's standard deviation, the
variance relatively, each at its worst channel.  Every answer of the
window is compared."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

KEEP_ALL = True
TINY = float(np.finfo(np.float32).tiny)


def finish(out):
    st = jax.device_get(out)
    return (np.asarray(st.count), np.asarray(st.mean),
            np.asarray(st.m2) / np.maximum(np.asarray(st.count), 1))


def moment_errors(ans, ref) -> dict:
    count, mean, var = (np.asarray(a, np.float64) for a in ans)
    rcount, rmean, rvar = (np.asarray(a, np.float64) for a in ref)
    rvar = np.maximum(rvar, TINY)
    return {
        "count_err": float(np.max(np.abs(count - rcount))),
        "mean_err": float(np.max(np.abs(mean - rmean) / np.sqrt(rvar))),
        "var_err": float(np.max(np.abs(var - rvar) / rvar)),
    }


def errors(loop, kept, control: bool = False) -> dict:
    """Worst errors over the kept answers ``[(pool index, answer)]``;
    with ``control`` the reference in bfloat16 stands in the program's
    place."""
    refs, ctl, worst = {}, {}, {}
    for j, ans in kept:
        if j not in refs:
            args = (loop.cfg, loop.shape, loop.keys[j], loop.graph, loop.pad)
            refs[j] = reference.reduced(*args, devices=loop.devices)
            if control:
                ctl[j] = reference.reduced(*args, jnp.bfloat16,
                                           devices=loop.devices)
        for k, v in moment_errors(ctl[j] if control else ans,
                                  refs[j]).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst
