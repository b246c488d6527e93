"""The forward and the backward kernel with parts taken out or written
another way, at the cell's layer on the chip — TIMING ONLY (most variants
compute something else; `fwd_bwd_ms` is the whole gradient on the host's
clock, forward kernel and JAX's sums included): where a call's time goes.
The variants are of the kernels AS THEY FIRST STOOD — eight tokens a loop
body with every state's update written out, 1.43 and 6.78 ms a call — which
is what `variants.jsonl` timed; the kernels as shipped walk a block's tokens
in a loop (2.10 and 5.65 ms a call, a seventh of the host's seconds).
    python3 variants_probe.py [out.jsonl] [variant,variant...]
`PROBE_TINY=1` rehearses on the CPU."""
import json, os, sys, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from ray_tpu.ops import selective_scan as ss

tiny = bool(os.environ.get("PROBE_TINY"))
out_path = sys.argv[1] if len(sys.argv) > 1 else None
B, T, C, N = (1, 64, 256, 16) if tiny else (1, 8192, 5120, 16)
ks = jax.random.split(jax.random.PRNGKey(0), 8)
inputs = (jax.nn.silu(jax.random.normal(ks[0], (B, T, C))),
          jax.random.normal(ks[1], (B, T, C)) - 4.0,
          -jnp.broadcast_to(jnp.arange(1.0, N + 1), (C, N)),
          jax.random.normal(ks[2], (B, T, N)),
          jax.random.normal(ks[3], (B, T, N)),
          jnp.ones((C,)), jax.random.normal(ks[4], (C,)) * 0.5)
EIGHT = range(8)


def make(exp=True, scalars=True, readout=True, softplus=True, write=True,
         order="states_outer", exp2=False):
    def kernel(b_ref, c_ref, s_ref, dt_ref, a_ref, skip_ref, bias_ref, y_ref,
               starts_ref, state, **_):
        @pl.when(pl.program_id(2) == 0)
        def _():
            state[...] = jnp.zeros_like(state)
        starts_ref[...] = state[...]
        states, tiles = a_ref.shape[:2]

        def tok(r):
            return ss._token(r, tiles)

        skip, bias = skip_ref[...], bias_ref[...]

        def decay(d, a_n):
            if exp2:
                return jnp.exp2(d * a_n)
            return jnp.exp(d * a_n) if exp else d * a_n + 1.0

        def group(g, _):
            at = g * (8 * states)
            s = [s_ref[g, tok(r), :] for r in EIGHT]
            raw = [dt_ref[g, tok(r), :] + bias for r in EIGHT]
            delta = [ss._softplus(x) if softplus else x for x in raw]
            written = [delta[r] * s[r] for r in EIGHT]
            y = [skip * s[r] for r in EIGHT]

            def b(r, n):
                return b_ref[at + r * states + n] if scalars else 0.5

            def c(r, n):
                return c_ref[at + r * states + n] if scalars else 0.25

            if order == "states_outer":
                for n in range(states):
                    h, a_n = state[n], a_ref[n]
                    for r in EIGHT:
                        h = decay(delta[r], a_n) * h
                        if write:
                            h = h + written[r] * b(r, n)
                        if readout:
                            y[r] = y[r] + h * c(r, n)
                    state[n] = h
            elif order == "tokens_outer":
                hs = [state[n] for n in range(states)]
                for r in EIGHT:
                    for n in range(states):
                        hs[n] = (decay(delta[r], a_ref[n]) * hs[n]
                                 + written[r] * b(r, n))
                        y[r] = y[r] + hs[n] * c(r, n)
                for n in range(states):
                    state[n] = hs[n]
            elif order == "states_fori":
                def one(n, y):
                    y = list(y)
                    h, a_n = state[n], a_ref[n]
                    for r in EIGHT:
                        h = (decay(delta[r], a_n) * h
                             + written[r] * b(r, n))
                        y[r] = y[r] + h * c(r, n)
                    state[n] = h
                    return tuple(y)
                y = list(jax.lax.fori_loop(0, states, one, tuple(y)))
            for r in EIGHT:
                y_ref[g, tok(r), :] = y[r]

        jax.lax.fori_loop(0, s_ref.shape[0], group, None)
    return kernel


def make_bwd(rebuild=True, walk=True, parts=True, sums=True, da=True,
             reuse=False, decay=True):
    """The backward kernel with stages taken out (timing only)."""
    def kernel(b_ref, c_ref, s_ref, dt_ref, a_ref, skip_ref, bias_ref,
               starts_ref, dy_ref, ds_ref, ddt_ref, db_ref, dc_ref, da_ref,
               d_state, held, deltas, writes, b_parts, c_parts, **_):
        @pl.when(pl.program_id(2) == 0)
        def _():
            d_state[...] = jnp.zeros_like(d_state)
            da_ref[...] = jnp.zeros_like(da_ref)

        (states, tiles), groups = a_ref.shape[:2], s_ref.shape[0]
        skip, bias = skip_ref[...], bias_ref[...]
        held[0] = starts_ref[...]

        def tok(r):
            return ss._token(r, tiles)

        def rebuild_(g, _):
            first = g * 8
            at = first * states
            delta = [ss._softplus(dt_ref[g, tok(r), :] + bias) for r in EIGHT]
            written = [delta[r] * s_ref[g, tok(r), :] for r in EIGHT]
            for r in EIGHT:
                deltas[first + r] = delta[r]
                writes[first + r] = written[r]
            for n in range(states):
                h, a_n = held[first, n], a_ref[n]
                for r in EIGHT:
                    h = (jnp.exp(delta[r] * a_n) * h
                         + written[r] * b_ref[at + r * states + n])
                    held[first + r + 1, n] = h

        if rebuild:
            jax.lax.fori_loop(0, groups, rebuild_, None)

        def walk_(step, _):
            g = groups - 1 - step
            first = g * 8
            at = first * states
            dy = [dy_ref[g, tok(r), :] for r in EIGHT]
            delta = [deltas[first + r] for r in EIGHT]
            written = [writes[first + r] for r in EIGHT]
            d_written = [jnp.zeros_like(dy[0]) for _ in EIGHT]
            d_delta = [jnp.zeros_like(dy[0]) for _ in EIGHT]
            for n in range(states):
                dh, a_n, da_n = d_state[n], a_ref[n], da_ref[n]
                after = held[first + 8, n] if reuse else None
                for r in reversed(EIGHT):
                    before = held[first + r, n]
                    if not reuse:
                        after = held[first + r + 1, n]
                    dh = dh + dy[r] * c_ref[at + r * states + n]
                    if parts:
                        c_parts[n, tok(r), :] = dy[r] * after
                        b_parts[n, tok(r), :] = dh * written[r]
                    d_written[r] = d_written[r] + dh * b_ref[at + r * states + n]
                    if decay:
                        dh = dh * jnp.exp(delta[r] * a_n)
                    through = dh * before
                    d_delta[r] = d_delta[r] + through * a_n
                    if da:
                        da_n = da_n + through * delta[r]
                    after = before
                d_state[n] = dh
                da_ref[n] = da_n
            for r in EIGHT:
                s = s_ref[g, tok(r), :]
                ds_ref[g, tok(r), :] = d_written[r] * delta[r] + skip * dy[r]
                ddt_ref[g, tok(r), :] = (
                    (d_delta[r] + d_written[r] * s)
                    * jax.nn.sigmoid(dt_ref[g, tok(r), :] + bias))
            if sums:
                rows = pl.ds(pl.multiple_of(first, 8), 8)
                for n in range(states):
                    db_ref[rows, n:n + 1] = ss._by_token(b_parts[n], tiles)
                    dc_ref[rows, n:n + 1] = ss._by_token(c_parts[n], tiles)

        if walk:
            jax.lax.fori_loop(0, groups, walk_, None)
    return kernel


BWD_VARIANTS = {
    "bwd_full": {},
    "bwd_reuse_held": dict(reuse=True),
    "bwd_no_parts": dict(parts=False),
    "bwd_no_sums": dict(sums=False),
    "bwd_no_parts_no_sums": dict(parts=False, sums=False),
    "bwd_no_da": dict(da=False),
    "bwd_no_decay": dict(decay=False),
    "bwd_rebuild_only": dict(walk=False),
    "bwd_walk_only": dict(rebuild=False),
    "bwd_neither": dict(rebuild=False, walk=False),
}
VARIANTS = {
    "full": {},
    "no_exp": dict(exp=False),
    "exp2": dict(exp2=True),
    "no_scalars": dict(scalars=False),
    "no_exp_no_scalars": dict(exp=False, scalars=False),
    "no_readout": dict(readout=False),
    "decay_only": dict(readout=False, write=False, scalars=False),
    "decay_only_no_exp": dict(readout=False, write=False, scalars=False,
                              exp=False),
    "no_softplus": dict(softplus=False),
    "tokens_outer": dict(order="tokens_outer"),
    "states_fori": dict(order="states_fori"),
}
which = (sys.argv[2].split(",") if len(sys.argv) > 2
         else list(VARIANTS) + list(BWD_VARIANTS))


def ms(fn, *a, n=2 if tiny else 5):
    jax.block_until_ready(fn(*a))
    t = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / n * 1e3


if tiny:
    ss.BLOCK_TOKENS, ss.BLOCK_TILES = 16, 1
lines = []
FWD, BWD = ss._fwd_kernel, ss._bwd_kernel
weights = jax.random.normal(ks[5], (B, T, C))
for name in which:
    ss._fwd_kernel, ss._bwd_kernel = FWD, BWD
    if name in VARIANTS:
        ss._fwd_kernel = make(**VARIANTS[name])
        fn = jax.jit(lambda *a: ss.selective_scan(*a, interpret=tiny))
    else:
        ss._bwd_kernel = make_bwd(**BWD_VARIANTS[name])
        fn = jax.jit(jax.grad(
            lambda *a: jnp.sum(ss.selective_scan(*a, interpret=tiny)
                               * weights), argnums=tuple(range(7))))
    jax.clear_caches()
    try:
        line = {"variant": name,
                "fwd_ms" if name in VARIANTS else "fwd_bwd_ms":
                round(ms(fn, *inputs), 3),
                "device": jax.devices()[0].device_kind}
    except Exception as e:
        line = {"variant": name, "refused": str(e)[:600]}
    print(json.dumps(line), flush=True)
    lines.append(line)
if out_path:
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
