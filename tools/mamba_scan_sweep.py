"""On the chip: ms a layer of a chunk's selective scan at the published
Jamba2-3B sizes (5120 channels, 16 state rows), every layer of 26 in
turn in one program: the XLA form ``serve/decode.py::mamba_scan`` (a
position at a time, the padding scanned with a step of 0) beside the
Pallas call ``ops/mamba_scan.py`` by the channels and positions a grid
step holds and the channels and positions its loop holds, at chunks of
512, 256 and 128 positions full and at a ``length`` of three quarters,
and the two forms' states and ``y`` compared on one input:

    chiprun -- python tools/mamba_scan_sweep.py

What ``ops/mamba_scan.py::_channels``, ``_POSITIONS`` and ``_WIDTHS``
were chosen from (PERF.md, PR 49). With ``--xla-forms``, the XLA forms
of the scan alone, one call at a time (``lax.scan`` by positions a loop
iteration beside ``lax.associative_scan`` inside blocks, the form PR 47
first built), and ``mamba_step`` over 257 slots: what
``decode._MAMBA_UNROLL`` was chosen from (PERF.md, PR 47). With
``--step``, the decode step alone at the cell's shapes (256 rows at
shuffled slots of 26 layers' pool of 257): the XLA form of a layer
(every slot's state where it lies, the rows carried to their slots: the
fall-back of ``decode.mamba_step_layer``) beside the Pallas call
``ops/mamba_step.py`` by the channels its loop and a grid step hold, ms
a layer and the GB/s of the states read and written; and the
convolution rows' update, the scatter by slot beside the layer's rows
rewritten where they lie:

    chiprun -- python tools/mamba_scan_sweep.py --step

What ``ops/mamba_step.py::_channels`` was chosen from (PERF.md, PR 48).
With ``--step --rule ssd`` or ``--rule kda``, the decode step of a
Mamba-2 or a kda layer alone at its cell's shapes (Nemotron's pool
``[5, 129, 128, 64, 128]`` at 128 and 64 rows, ling's ``[6, 65, 32,
128, 128]`` at 64 and 16): the XLA form of a layer (``decode.ssd_step``
/ ``decode.kda_step`` on every slot, the rows carried to their slots)
beside the Pallas call ``ops/state_step.py`` by the heads a grid step
holds:

    chiprun -- python tools/mamba_scan_sweep.py --step --rule ssd

What ``ops/state_step.py::_SSD_HEADS`` and ``_KDA_HEADS`` were chosen
from (PERF.md, PR 62). With ``--scan --rule ssd``, a chunk's SSD of a
Mamba-2 layer at Nemotron's sizes (``x [1, T, 128, 64]``, ``B`` and
``C`` ``[1, T, 8, 128]``, a resumed state ``[1, 128, 64, 128]``, blocks
of 128; ``T`` of 1024, 512 and 256, full and a ``length`` short of the
bucket): the XLA form ``decode.ssd_scan`` beside the Pallas call
``ops/ssd_scan.py`` by the heads a grid step holds, each with the
layer's ``D x``, ms a layer inside a program of five and as the
difference of a program of twenty and one of five (a program's launch
costs the chip's machine 0.8 ms whatever it holds), and both forms' gaps
to the recurrence a position at a time in float64:

    chiprun -- python tools/mamba_scan_sweep.py --scan --rule ssd

What ``ops/ssd_scan.py::_HEADS`` was chosen from (PERF.md, PR 64). Off
the chip the interpreter takes an hour at these sizes: rehearse with
``--layers 1 --channels 1024`` (or ``--layers 2 --slots 16`` with
``--step``, ``--layers 2 --heads 8 --lengths 256`` with ``--scan --rule
ssd``)."""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from horovod_tpu.ops import mamba_scan as scan_lib  # noqa: E402
from horovod_tpu.ops import mamba_step as step_lib  # noqa: E402
from horovod_tpu.ops import ssd_scan as ssd_lib  # noqa: E402
from horovod_tpu.ops import state_step as state_lib  # noqa: E402
from horovod_tpu.serve import decode as decode_lib  # noqa: E402

DI, N = 5120, 16


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


def blocked(u, step, a, b, c, state, block):
    """``lax.associative_scan`` over ``(decay, drive)`` pairs inside
    blocks of ``block`` positions, the state carried between blocks."""
    B, T, Di = u.shape
    n = T // block

    def blocks(x):
        return jnp.moveaxis(x.reshape(B, n, block, -1), 1, 0)

    def then(first, second):
        return (first[0] * second[0], second[0] * first[1] + second[1])

    def one_block(s, xs):
        u, step, b, c = xs
        decay = jnp.exp(step[:, :, None] * a)
        drive = (step * u)[:, :, None] * b[..., None]
        decay, drive = jax.lax.associative_scan(then, (decay, drive), axis=1)
        states = decay * s[:, None] + drive
        return states[:, -1], jnp.sum(states * c[..., None], axis=2)

    state, y = jax.lax.scan(one_block, state,
                            tuple(map(blocks, (u, step, b, c))))
    return jnp.moveaxis(y, 0, 1).reshape(B, T, Di), state


def carried(layer_fn, pool, at, lead, *rest, n=10):
    """ms a layer of ``layer_fn(pool, layer, at, lead, *rest) -> (pool,
    result)`` run on every layer of ``pool`` in turn in one program (a
    call of one layer alone is over before the host has launched the
    next: 0.3 ms), a layer's ``lead`` nudged by the result of the layer
    before it as a decode program's is, the pool donated and handed on
    from call to call."""
    layers = pool.shape[0]

    def every_layer(pool, at, lead, *rest):
        nudge = jnp.zeros_like(lead)
        for layer in range(layers):
            pool, result = layer_fn(pool, layer, at, lead + nudge, *rest)
            nudge = (1e-6 * result[:, :lead.shape[1]]).astype(lead.dtype)
        return pool, nudge

    fn = jax.jit(every_layer, donate_argnums=(0,))
    out = fn(pool, at, lead, *rest)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(out[0], at, lead, *rest)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n / layers


def step_sweep(layers=26, slots=256, conv=4):
    """The decode step of one layer of ``layers``' pool, by form, at a
    batch of every slot and of a quarter of them."""
    rows = (slots, slots // 4)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    a = -jnp.exp(jax.random.normal(ks[0], (N, DI)))
    for B in rows:
        at = jax.random.permutation(ks[1], slots)[:B].astype(jnp.int32) + 1
        u = jax.random.normal(ks[2], (B, DI))
        step = jax.random.uniform(ks[3], (B, DI), minval=1e-3, maxval=0.1)
        b = jax.random.normal(ks[4], (B, N))
        c = jax.random.normal(ks[5], (B, N))
        moved = 2 * B * N * DI * 4

        def xla(pool, layer, at, u, step, b, c):
            def by_slot(x):
                return jnp.zeros((slots + 1,) + x.shape[1:],
                                 x.dtype).at[at].set(x)
            y, state = decode_lib.mamba_step(
                by_slot(u), by_slot(step), a, by_slot(b), by_slot(c),
                pool[layer])
            return pool.at[layer].set(state), y[at]

        def report(form, fn, **how):
            pool = jax.random.normal(ks[6], (layers, slots + 1, N, DI))
            ms = carried(fn, pool, at, u, step, b, c)
            print(json.dumps({"rows": B, "form": form, **how,
                              "ms_a_layer": round(ms, 4),
                              "GBps_of_the_rows_states":
                                  round(moved / ms / 1e6, 1)}), flush=True)

        # the two forms on one pool, before either is timed
        pool = jax.random.normal(ks[6], (2, slots + 1, N, DI))
        want, y_want = jax.jit(xla)(pool, 1, at, u, step, b, c)
        y_got, got = jax.jit(step_lib.mamba_step)(u, step, a, b, c, pool, 1,
                                                  at)
        print(json.dumps({
            "rows": B, "kernel_against_xla": {
                "y_max_gap": float(jnp.abs(y_got - y_want).max()),
                "state_max_gap": float(jnp.abs(got - want).max())}}),
            flush=True)
        del pool, want, got
        report("xla, every slot", xla)
        for block, channels in ((DI, 256), (DI, 512), (DI, DI),
                                (2560, 512), (1280, 256)):
            def kernel(pool, layer, at, u, step, b, c, block=block,
                       channels=channels):
                y, pool = step_lib.mamba_step(
                    u, step, a, b, c, pool, layer, at, block=block,
                    channels=channels)
                return pool, y
            report("hvd_mamba_step", kernel, channels_a_grid_step=block,
                   channels_a_loop=channels)

        # the convolution's rows: bf16 [layers, slots + 1, (conv - 1) Di]
        new = jax.random.normal(ks[7], (B, DI)).astype(jnp.bfloat16)

        def scatter(rows, mid, at, new):
            before = rows[mid, at]
            return rows.at[mid, at].set(
                jnp.concatenate([before[:, DI:], new], 1)), before

        def select(rows, mid, at, new):
            # XLA's form of the kernel: the layer's rows rewritten, a
            # select a slot (in a decode program of 26 layers the
            # compiler copied the whole array for two of them)
            stepped = jnp.zeros((slots + 1, 1), bool).at[at].set(True)
            last = jnp.zeros((slots + 1, DI), rows.dtype).at[at].set(new)
            return rows.at[mid].set(jnp.where(stepped, jnp.concatenate(
                [rows[mid][:, DI:], last], 1), rows[mid])), rows[mid, at]

        def kernel(rows, mid, at, new):
            before = rows[mid, at]
            return step_lib.shift_rows(rows, mid, at, new), before

        def gather(rows, mid, at, new):
            return rows, rows[mid, at]

        pool = jax.random.normal(ks[6], (layers, slots + 1, (conv - 1) * DI)
                                 ).astype(jnp.bfloat16)
        print(json.dumps({"rows": B, "hvd_mamba_rows_is_the_scatter": bool(
            (jax.jit(kernel, static_argnums=1)(pool, 1, at, new)[0]
             == jax.jit(scatter, static_argnums=1)(pool, 1, at, new)[0]
             ).all())}), flush=True)
        del pool
        for form, fn in (("the gather of the rows before, alone", gather),
                         ("gather and scatter by slot", scatter),
                         ("gather and the layer's rows selected in XLA",
                          select),
                         ("gather and hvd_mamba_rows", kernel)):
            pool = jnp.zeros((layers, slots + 1, (conv - 1) * DI),
                             jnp.bfloat16)
            ms = carried(fn, pool, at, new)
            print(json.dumps({"rows": B, "conv_rows": form,
                              "ms_a_layer": round(ms, 4)}), flush=True)


#: The heads a grid step of ``hvd_state_step`` holds, by rule.
STATE_HEADS = {"ssd": (8, 16, 32, 64), "kda": (8, 16, 32)}


def state_step_inputs(rule, key, B, heads, rows, cols, groups=8):
    """The per-row inputs of ``rule`` for ``B`` rows, in the order
    ``decode.ssd_step`` / ``decode.kda_step`` and the kernels take them;
    the first is the one a layer's result nudges."""
    ks = jax.random.split(key, 5)
    if rule == "ssd":
        return (jax.random.normal(ks[0], (B, heads, rows)),
                jax.random.uniform(ks[1], (B, heads), minval=1e-3,
                                   maxval=0.1),
                -jnp.exp(jax.random.normal(ks[2], (heads,))),
                jax.random.normal(ks[3], (B, groups, cols)),
                jax.random.normal(ks[4], (B, groups, cols)))
    unit = jax.random.normal(ks[1], (B, heads, rows))
    return (jax.random.normal(ks[0], (B, heads, rows)) * rows ** -0.5,
            unit / jnp.linalg.norm(unit, axis=-1, keepdims=True),
            jax.random.normal(ks[2], (B, heads, cols)),
            -jnp.exp(jax.random.normal(ks[3], (B, heads, rows))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (B, heads))))


def state_step_sweep(rule, layers=None, slots=None):
    """The decode step of one Mamba-2 (``ssd``) or kda layer of
    ``layers``' pool, by form, at a batch of every slot but the null
    one and of half or a quarter of them."""
    xla_step, kernel, shape, fewer = {
        "ssd": (decode_lib.ssd_step, state_lib.ssd_step, (128, 64, 128), 2),
        "kda": (decode_lib.kda_step, state_lib.kda_step, (32, 128, 128), 4),
    }[rule]
    layers = layers or {"ssd": 5, "kda": 6}[rule]
    slots = slots or {"ssd": 128, "kda": 64}[rule]
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    # the inputs a row has one of (ssd's ``a`` is the layer's)
    by_row = (0, 1, 3, 4) if rule == "ssd" else (0, 1, 2, 3, 4)
    for B in (slots, slots // fewer):
        at = jax.random.permutation(ks[0], slots)[:B].astype(jnp.int32) + 1
        inputs = state_step_inputs(rule, ks[1], B, *shape)
        moved = 2 * B * 4 * shape[0] * shape[1] * shape[2]

        def xla(pool, layer, at, lead, *rest):
            def by_slot(x):
                return jnp.zeros((slots + 1,) + x.shape[1:],
                                 x.dtype).at[at].set(x)
            out, state = xla_step(
                *(by_slot(x) if n in by_row else x for n, x in enumerate(
                    (lead.reshape(inputs[0].shape), *rest))), pool[layer])
            return pool.at[layer].set(state), out[at].reshape(B, -1)

        def through(fn, **how):
            def layer_fn(pool, layer, at, lead, *rest):
                out, pool = fn(lead.reshape(inputs[0].shape), *rest, pool,
                               layer, at, **how)
                return pool, out.reshape(B, -1)
            return layer_fn

        def report(form, fn, **how):
            pool = jax.random.normal(ks[2], (layers, slots + 1) + shape)
            ms = carried(fn, pool, at, inputs[0].reshape(B, -1),
                         *inputs[1:])
            print(json.dumps({"rule": rule, "rows": B, "form": form, **how,
                              "ms_a_layer": round(ms, 4),
                              "GBps_of_the_rows_states":
                                  round(moved / ms / 1e6, 1)}), flush=True)

        # the forms on one pool, before any is timed
        pool = jax.random.normal(ks[2], (2, slots + 1) + shape)
        want, out_want = jax.jit(xla)(pool, 1, at, *inputs)
        out, got = jax.jit(kernel)(*inputs, pool, 1, at)
        print(json.dumps({
            "rule": rule, "rows": B, "kernel_against_xla": {
                "out_max_gap": float(jnp.abs(
                    out.reshape(B, -1) - out_want).max()),
                "state_max_gap": float(jnp.abs(got - want).max())}}),
            flush=True)
        del pool, want, got
        report("xla, every slot", xla)
        for heads in STATE_HEADS[rule]:
            report("hvd_state_step", through(kernel, heads=heads),
                   heads_a_grid_step=heads)


#: The kernel's forms the sweep times: channels a grid step, positions a
#: grid step, channels a loop carries in registers, positions a loop
#: iteration.
KERNEL_FORMS = (
    (5120, 64, 512, 8), (5120, 128, 512, 8), (5120, 32, 512, 8),
    (5120, 64, 256, 8), (5120, 64, 1024, 8), (5120, 64, 512, 1),
    (5120, 64, 512, 4), (5120, 64, 512, 16), (2560, 64, 512, 8),
    (1024, 64, 512, 8), (512, 64, 512, 8))


def scan_sweep(layers=26, d_inner=DI):
    """A chunk's scan of every layer in turn in one program, a layer's
    input nudged by the result of the layer before it (one layer's call
    alone is over before the host has launched the next): the XLA form
    ``decode.mamba_scan`` with the padding's step zeroed, as
    ``mamba_chunk`` wrote it, beside the Pallas call
    ``ops/mamba_scan.py`` by its block sizes, at a full bucket and at a
    ``length`` of three quarters of it. Every form at the largest
    bucket; the other buckets the XLA form, the program's own sizes and
    the two fastest."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    a = -jnp.exp(jax.random.normal(ks[0], (N, d_inner)))

    def every_layer(scan):
        def run(states, u, step, b, c, length):
            nudge, out = jnp.zeros_like(u), []
            for layer in range(layers):
                y, state = scan(u + nudge, step, b, c, states[layer], length)
                out.append(state)
                nudge = 1e-6 * y
            return jnp.stack(out), nudge
        return jax.jit(run, donate_argnums=(0,))

    def xla(u, step, b, c, state, length):
        real = jnp.arange(u.shape[1])[None, :, None] < length
        return decode_lib.mamba_scan(u, jnp.where(real, step, 0.0), a, b, c,
                                     state)

    def kernel(channels=None, block=None, width=None, unroll=8):
        def scan(u, step, b, c, state, length):
            return scan_lib.mamba_scan(
                u, step, a, b, c, state, length, channels=channels,
                block=block, width=width, unroll=unroll)
        return scan

    def ms_a_layer(fn, inputs, length, n=10):
        states = jax.random.normal(ks[5], (layers, 1, N, d_inner))
        out = fn(states, *inputs, length)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(out[0], *inputs, length)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / n / layers

    fastest = []
    for T in (512, 256, 128):
        inputs = (jax.random.normal(ks[1], (1, T, d_inner)),
                  jax.random.uniform(ks[2], (1, T, d_inner), minval=1e-3,
                                     maxval=0.1),
                  jax.random.normal(ks[3], (1, T, N)),
                  jax.random.normal(ks[4], (1, T, N)))
        lengths = (jnp.int32(T), jnp.int32(3 * T // 4))
        # the two forms on one input, before either is timed
        state = jax.random.normal(ks[5], (1, N, d_inner))
        for length in lengths:
            y_want, want = jax.jit(xla)(*inputs, state, length)
            y_got, got = jax.jit(kernel())(*inputs, state + 0.0, length)
            real = jnp.arange(T)[None, :, None] < length
            print(json.dumps({"T": T, "length": int(length),
                              "kernel_against_xla": {
                "state_max_gap": float(jnp.abs(got - want).max()),
                "y_max_gap_at_real_positions": float(
                    jnp.abs(jnp.where(real, y_got - y_want, 0.0)).max()),
                "y_max_past_length": float(
                    jnp.abs(jnp.where(real, 0.0, y_got)).max())}}),
                flush=True)
        row = {"T": T, "form": "xla, decode.mamba_scan"}
        for length in lengths:
            row[f"ms_a_layer_at_{int(length)}"] = round(
                ms_a_layer(every_layer(xla), inputs, length), 4)
        print(json.dumps(row), flush=True)
        rows = []
        for form in [None] + fastest if fastest else KERNEL_FORMS:
            if form is not None and (form[0] > d_inner or form[1] > T):
                continue
            scan = kernel(*(form or ()))
            row = {"T": T, "form": "hvd_mamba_scan"}
            if form is None:
                row["sizes"] = "the program's own"
            else:
                row.update(zip(("channels_a_grid_step", "positions_a_grid_"
                                "step", "channels_a_loop", "unroll"), form))
            for length in lengths:
                row[f"ms_a_layer_at_{int(length)}"] = round(
                    ms_a_layer(every_layer(scan), inputs, length), 4)
            print(json.dumps(row), flush=True)
            rows.append((row[f"ms_a_layer_at_{T}"], form))
        if not fastest:
            fastest = [form for _, form in sorted(rows)[:2]]


#: The heads a grid step of ``hvd_ssd_scan`` holds.
SSD_SCAN_HEADS = (8, 16, 32)


def ssd_recurrence(x, dt, a, b, c, state, length):
    """The recurrence a position at a time in float64, on the host:
    ``(y [T, Hm, P], the state after position length - 1)`` of row 0."""
    import numpy as np
    x, dt, a, b, c, state = (np.asarray(v, np.float64)
                             for v in (x[0], dt[0], a, b[0], c[0], state[0]))
    per_group = x.shape[1] // b.shape[1]
    y = np.zeros(x.shape)
    for t in range(length):
        bt, ct = (np.repeat(v[t], per_group, axis=0)[:, None] for v in (b, c))
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t, :, None] * x[t])[..., None] * bt)
        y[t] = (state * ct).sum(-1)
    return y, state


def ssd_scan_sweep(layers=None, n_heads=None, lengths=None):
    """A chunk's SSD of every layer in turn in one program, a layer's
    ``Delta`` nudged by the result of the layer before it (a few KB: at
    these sizes a nudge of ``x`` itself, 32 MB read and written a layer,
    costs more than the scan): ``decode.ssd_scan`` with the padding's
    step zeroed, as ``mamba2_chunk`` writes it, beside
    ``ops/ssd_scan.py`` by the heads a grid step holds, at a full bucket
    and at a ``length`` an eighth and a block short of it. A program of
    five layers costs the chip's machine about 0.8 ms to launch whatever
    it holds, so ms a layer is read twice: of a program of five, and as
    the difference of a program of twenty and one of five."""
    import numpy as np
    layers = layers or 5
    Hm = n_heads or 128
    P, G, N, block = 64, 8, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    a = -jnp.exp(jax.random.normal(ks[0], (Hm,)))
    skip = jax.random.normal(ks[6], (Hm,))

    def every_layer(scan, layers):
        def run(states, x, dt, b, c, length):
            out = []
            for layer in range(layers):
                y, state = scan(x, dt, b, c, states[layer], length)
                out.append(state)
                dt = dt + 1e-9 * jnp.abs(y[:, :, :, 0])
            return jnp.stack(out), dt
        return jax.jit(run, donate_argnums=(0,))

    def xla(x, dt, b, c, state, length):
        real = jnp.arange(x.shape[1])[None, :, None] < length
        y, state = decode_lib.ssd_scan(x, jnp.where(real, dt, 0.0), a, b, c,
                                       state, block)
        return y + skip[:, None] * x, state

    def kernel(heads=None):
        def scan(x, dt, b, c, state, length):
            real = jnp.arange(x.shape[1])[None, :, None] < length
            return ssd_lib.ssd_scan(x, jnp.where(real, dt, 0.0), a, b, c,
                                    state, length, block=block, skip=skip,
                                    heads=heads)
        return scan

    def ms_a_program(scan, layers, inputs, length, n=20):
        fn = every_layer(scan, layers)
        states = jax.random.normal(ks[5], (layers, 1, Hm, P, N))
        out = fn(states, *inputs, length)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(out[0], *inputs, length)
        jax.block_until_ready(out)
        return 1e3 * (time.perf_counter() - t0) / n

    for T in lengths or (1024, 512, 256):
        inputs = (jax.random.normal(ks[1], (1, T, Hm, P)),
                  jax.random.uniform(ks[2], (1, T, Hm), minval=1e-3,
                                     maxval=0.1),
                  jax.random.normal(ks[3], (1, T, G, N)),
                  jax.random.normal(ks[4], (1, T, G, N)))
        short = sorted({T - T // 8 - 1, max(T - block - 1, 1)})
        state = jax.random.normal(ks[5], (1, Hm, P, N))
        # the forms on one input against float64, before any is timed
        for length in (T, *short):
            y_ref, ref = ssd_recurrence(*inputs[:2], a, *inputs[2:], state,
                                        length)
            y_ref += np.asarray(skip, np.float64)[:, None] * np.asarray(
                inputs[0][0], np.float64)
            row = {"T": T, "length": length,
                   "y_max": float(np.abs(y_ref).max()),
                   "state_max": float(np.abs(ref).max())}
            got = {}
            for form, scan in (("xla", xla), ("kernel", kernel())):
                y, new = jax.jit(scan)(*inputs, state + 0.0,
                                       jnp.int32(length))
                got[form] = (y, new)
                row[form + "_against_float64"] = {
                    "y_max_gap": float(np.abs(
                        np.asarray(y[0, :length]) - y_ref[:length]).max()),
                    "state_max_gap": float(np.abs(
                        np.asarray(new[0]) - ref).max())}
            row["kernel_against_xla"] = {
                "y_max_gap": float(jnp.abs(
                    got["kernel"][0][:, :length]
                    - got["xla"][0][:, :length]).max()),
                "state_max_gap": float(jnp.abs(
                    got["kernel"][1] - got["xla"][1]).max()),
                "y_max_in_skipped_blocks": float(jnp.abs(
                    got["kernel"][0][:, -(-length // block) * block:]).max(
                        initial=0.0))}
            print(json.dumps(row), flush=True)
        for form, scan, how in (
                [("xla, decode.ssd_scan", xla, {})]
                + [("hvd_ssd_scan", kernel(heads), {"heads_a_grid_step":
                                                    heads})
                   for heads in SSD_SCAN_HEADS if Hm % heads == 0]):
            row = {"T": T, "form": form, **how}
            for length in (T, *short):
                few, many = (ms_a_program(scan, n, inputs, jnp.int32(length))
                             for n in (layers, 4 * layers))
                row[f"ms_a_layer_of_{layers}_at_{length}"] = round(
                    few / layers, 4)
                row[f"ms_a_layer_by_difference_at_{length}"] = round(
                    (many - few) / (3 * layers), 4)
            print(json.dumps(row), flush=True)


def xla_forms_sweep():
    """One call at a time: the scan in XLA by positions a loop iteration
    beside ``lax.associative_scan`` inside blocks, and ``mamba_step``
    over 257 slots."""
    key = jax.random.PRNGKey(0)
    a = -jnp.exp(jax.random.normal(key, (N, DI)))
    for T in (512, 4096):
        ks = jax.random.split(key, 4)
        u = jax.random.normal(ks[0], (1, T, DI))
        step = jax.random.uniform(ks[1], (1, T, DI), minval=1e-3, maxval=0.1)
        b = jax.random.normal(ks[2], (1, T, N))
        c = jax.random.normal(ks[3], (1, T, N))
        s0 = jnp.zeros((1, N, DI))
        row = {"T": T}
        for block in (8, 64):
            if block > T:
                continue
            fn = jax.jit(lambda *xs, block=block: blocked(*xs, block=block))
            row[f"blocks_of_{block}_ms"] = round(
                timed(fn, u, step, a, b, c, s0), 3)
        for unroll in (1, 4, 8, 16, 32):
            fn = jax.jit(lambda *xs, unroll=unroll: decode_lib.mamba_scan(
                *xs, unroll=unroll))
            row[f"unroll_{unroll}_ms"] = round(
                timed(fn, u, step, a, b, c, s0), 3)
        print(json.dumps(row), flush=True)
    S = 257
    ks = jax.random.split(key, 5)
    args = (jax.random.normal(ks[0], (S, DI)),
            jax.random.uniform(ks[1], (S, DI), minval=1e-3, maxval=0.1), a,
            jax.random.normal(ks[2], (S, N)), jax.random.normal(ks[3], (S, N)),
            jax.random.normal(ks[4], (S, N, DI)))
    fn = jax.jit(decode_lib.mamba_step, donate_argnums=(5,))
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(50):
        out = fn(*args[:5], out[1])
    jax.block_until_ready(out)
    ms = 1e3 * (time.perf_counter() - t0) / 50
    print(json.dumps({"mamba_step_257_slots_ms": round(ms, 3),
                      "GBps": round(2 * S * N * DI * 4 / ms / 1e6, 1)}))



def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--step", action="store_true",
                        help="the decode step's forms, not the scan's")
    parser.add_argument("--scan", action="store_true",
                        help="with --rule ssd: a chunk's SSD "
                             "(ops/ssd_scan.py)")
    parser.add_argument("--heads", type=int, default=None,
                        help="with --scan --rule ssd: the layer's heads, a "
                             "rehearsal's, off the chip")
    parser.add_argument("--lengths", type=int, nargs="+", default=None,
                        help="with --scan --rule ssd: the chunks' widths")
    parser.add_argument("--xla-forms", action="store_true",
                        help="the chunk's scan in XLA alone, by form: what "
                             "PR 47 chose among")
    parser.add_argument("--rule", choices=("mamba", "ssd", "kda"),
                        default="mamba",
                        help="with --step: the selective scan's step "
                             "(ops/mamba_step.py), or Mamba-2's or a kda "
                             "layer's (ops/state_step.py)")
    parser.add_argument("--layers", type=int, default=None)
    parser.add_argument("--slots", type=int, default=None,
                        help="with --layers: a rehearsal's size, off the chip")
    parser.add_argument("--channels", type=int, default=DI,
                        help="the scan's channels: a rehearsal's, off the "
                             "chip")
    args = parser.parse_args()
    if args.scan and args.rule == "ssd":
        return ssd_scan_sweep(args.layers, args.heads, args.lengths)
    if args.step and args.rule != "mamba":
        return state_step_sweep(args.rule, args.layers, args.slots)
    if args.step:
        return step_sweep(args.layers or 26, args.slots or 256)
    if args.xla_forms:
        return xla_forms_sweep()
    return scan_sweep(args.layers or 26, args.channels)


if __name__ == "__main__":
    main()
