"""Plain float32 reference of the IRC detector's population path.

Written from the paper (arXiv:2205.03996, Secs. III-IV, Fig. 11) and the
configuration file alone; it imports nothing of the program under test and
takes none of its state.  Given the benchmark's weights, the images and a
die's PRNG coordinates it computes what one sampled die answers:

  stem      3x3/2 conv + batch norm with calibration statistics + step
  6 layers  group convs of 60 channels, each group one crossbar:
            im2col rows (tap-major, then channel), always-on lead rows,
            per-die log-normal LRS variation and HRS leak, 32-row IR-drop
            blocks, accumulation nonlinearity (single shot or partial
            sums), SA offset and sensing-range failures
  head      1x1 conv to YOLO predictions

Die `c` of a population keyed `key` draws group `g` of layer `l = 10*s+b`
from `fold_in(fold_in(fold_in(key, c), l), g)`, split three ways: the
positive plane's variation, the negative plane's and the per-read SA draws.

Precision follows the configuration's `precision` entry: crossbar products
exact in float32, digital stem and head on bfloat16 operands with float32
sums.  `products="three_pass"` is the control: crossbar products on a
bfloat16 high/low split of the conductances, as a three-pass float32
matmul computes them.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from images import ANCHORS

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5


class Physics:
    """The configuration file's numbers, as the reference uses them."""

    def __init__(self, conf: Dict):
        net = conf["network"]
        self.img_hw = tuple(net["img_hw"])
        self.n_classes = net["n_classes"]
        self.n_anchors = net["n_anchors"]
        self.group = net["group"]
        self.stages = tuple(net["stage_channels"])
        self.blocks = tuple(net["blocks_per_stage"])
        self.scheme = net["scheme"]
        self.use_bn = net["use_bn"]
        self.accumulation = net["accumulation"]
        self.bias_rows = net["bias_rows"]
        self.partial_rows = net["partial_rows"]
        self.lo_frac, _, self.hi_frac = conf["ternary_fractions"]
        m = conf["macro"]
        self.sigma = m["sigma_lrs"]
        self.leak = m["hrs_leak"]
        self.block = m["ir_block"]
        self.alpha = m["ir_alpha"]
        self.sense_lo = m["sense_low_ua"] / m["i_lrs_ua"]
        self.sense_hi = m["sense_high_ua"] / m["i_lrs_ua"]
        self.bn_rows = m["bn_rows"]
        self.sa = (m["sa_c0"], m["sa_c1"], m["sa_c2"])
        nl = conf["nonlinearity"]
        self.nl_split, self.nl_max = nl["split_p"], nl["p_max"]
        self.nl_lo, self.nl_hi = tuple(nl["lo"]), tuple(nl["hi"])
        self.digital_bf16 = conf["precision"]["digital"].startswith("bfloat16")

    def layers(self):
        """(name, layer id, input channels after widening, channels, whether
        the stage's 2x2 pool follows) of each IRC layer, in order."""
        for s, (ch, nb) in enumerate(zip(self.stages, self.blocks)):
            c_in = self.stages[s - 1] if s else ch
            for b in range(nb):
                yield (f"s{s}b{b}", 10 * s + b,
                       max(c_in if b == 0 else ch, ch), ch, b == nb - 1)

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in vars(self).items())))

    def __eq__(self, other):
        return isinstance(other, Physics) and vars(self) == vars(other)


# ------------------------------------------------------------------ digital
# "bfloat16 operands, float32 sums" (as a TPU computes a float32 matmul or
# convolution at its default precision): operands are rounded to bfloat16
# and their exact products summed in float32; a backward pass rounds the
# incoming gradient the same way.

def _bf16(x):
    """Round float32 to the nearest bfloat16 value (an explicit rounding op:
    a float32 -> bfloat16 -> float32 round trip may be elided as excess
    precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


@jax.custom_vjp
def _round_grad(y):
    return y


_round_grad.defvjp(lambda y: (y, None), lambda _, g: (_bf16(g),))


def _operands(phys: Physics, *xs):
    if not phys.digital_bf16:
        return xs
    return tuple(x + jax.lax.stop_gradient(_bf16(x) - x) for x in xs)


def _result(phys: Physics, y):
    return _round_grad(y) if phys.digital_bf16 else y


def digital_conv(phys: Physics, x, w, stride: int):
    x, w = _operands(phys, x, w)
    return _result(phys, jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST))


def digital_dense(phys: Physics, x, w):
    x, w = _operands(phys, x, w)
    return _result(phys, jnp.dot(x, w, precision=HIGHEST))


def step(x):
    return (x > 0).astype(jnp.float32)


def maxpool2(x):
    """2x2/2 max pool over [..., H, W, C] (SAME: an odd edge keeps its last
    row).  Its gradient goes to the first maximum of each window, as a
    max pool's backward pass routes it; with 0/1 activations ties are
    the rule, not the exception."""
    win = (1,) * (x.ndim - 3) + (2, 2, 1)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, win, win, "SAME")


def im2col(x):
    """[B, H, W, C] -> [B*H*W, 9*C]: 3x3 SAME patches, tap-major rows
    (row = (3*dy + dx) * C + channel)."""
    B, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    taps = [xp[:, dy:dy + H, dx:dx + W, :]
            for dy in range(3) for dx in range(3)]
    return jnp.concatenate(taps, axis=-1).reshape(B * H * W, 9 * C)


# ------------------------------------------------------------------ weights

def ternary_levels(phys: Physics, w):
    """Per-group 20/60/20 regulation: the lowest `lo_frac` of a group's
    latent weights go to -1, the highest `hi_frac` to +1, the rest to 0.
    The cut points are order statistics at index round(frac * (n - 1))."""
    flat = jnp.sort(w.reshape(-1, w.shape[-1]), axis=0)      # per group
    n = flat.shape[0]
    lo = flat[min(int(phys.lo_frac * (n - 1) + 0.5), n - 1)]
    hi = flat[min(int((1.0 - phys.hi_frac) * (n - 1) + 0.5), n - 1)]
    return jnp.where(w <= lo, -1.0, jnp.where(w >= hi, 1.0, 0.0))


def quantized(phys: Physics, w):
    """Latent [9*group, group, n_groups] -> deployed levels."""
    if phys.scheme == "ternary":
        return ternary_levels(phys, w)
    return jnp.where(w >= 0, 1.0, -1.0)


def group_planes(phys: Physics, wq_g, bn_units=None) -> Tuple:
    """One group's deployed weights [9*group, group] -> LRS placement
    planes (g_pos, g_neg) [lead + 9*group, group] and the lead row count.

    ternary: +1 -> (LRS, HRS), -1 -> (HRS, LRS), 0 -> (HRS, HRS), after
    `bias_rows` always-on rows that are LRS on both lines.
    binary:  the conv line holds LRS for +1; the shared reference line is
    half-conductance cells; in-memory BN adds `bn_rows` leading rows, |b|
    of them LRS on the conv line (b > 0) or on the reference line (b < 0).
    """
    if phys.scheme == "ternary":
        gp = (wq_g > 0.5).astype(jnp.float32)
        gn = (wq_g < -0.5).astype(jnp.float32)
        lead = phys.bias_rows
        ones = jnp.ones((lead, gp.shape[1]), jnp.float32)
        return (jnp.concatenate([ones, gp]), jnp.concatenate([ones, gn]),
                lead)
    gp = (wq_g > 0).astype(jnp.float32)
    gn = jnp.full(gp.shape, 0.5, jnp.float32)
    if bn_units is None:
        return gp, gn, 0
    lead = phys.bn_rows
    b = jnp.clip(jnp.round(bn_units), -lead, lead)
    r = jnp.arange(lead, dtype=jnp.float32)[:, None]
    gp = jnp.concatenate([(r < jnp.maximum(b, 0)).astype(jnp.float32), gp])
    gn = jnp.concatenate([(r < jnp.maximum(-b, 0)).astype(jnp.float32), gn])
    return gp, gn, lead


# ------------------------------------------------------------------ physics

def die_key(key, die, layer_id: int, g: int):
    k = jax.random.fold_in(key, die)
    return jax.random.fold_in(jax.random.fold_in(k, layer_id), g)


def sample_planes(phys: Physics, effects: Dict, k, gp, gn):
    """One die's effective conductances: programmed LRS cells scaled by a
    median-1 log-normal mask, HRS cells at the leak floor.  The binary
    design's reference line is one physical column, so its mask is shared
    by every output."""
    k_p, k_n, k_sa = jax.random.split(k, 3)
    ep, en = gp, gn
    if effects["device_variation"]:
        ep = gp * jnp.exp(phys.sigma * jax.random.normal(k_p, gp.shape))
        n_shape = (gn.shape[0], 1) if phys.scheme == "binary" else gn.shape
        en = gn * jnp.exp(phys.sigma * jax.random.normal(k_n, n_shape))
    ep = ep + (1.0 - gp) * phys.leak
    en = en + (1.0 - gn) * phys.leak
    return ep, en, k_sa


def block_currents(phys: Physics, x_ext, plane, products: str):
    """x_ext [P, R], plane [R, N] -> per-block currents [P, nb, N]."""
    R, N = plane.shape
    nb = -(-R // phys.block)
    pad = nb * phys.block - R
    xb = jnp.pad(x_ext, ((0, 0), (0, pad))).reshape(-1, nb, phys.block)
    pb = jnp.pad(plane, ((0, pad), (0, 0))).reshape(nb, phys.block, N)
    dot = lambda p: jnp.einsum("pbk,bkn->pbn", xb, p, precision=HIGHEST)
    if products == "three_pass":
        # x is 0/1, exact in bfloat16: what a three-pass float32 matmul
        # keeps of each product is x times the two-term bfloat16 split
        hi = _bf16(pb)
        lo = _bf16(pb - hi)
        return dot(hi) + dot(lo)
    return dot(pb)


def ir_retention(phys: Physics, blocks):
    """Block b (0 nearest the driver) sees the wire drop of every segment
    up to it; segment k carries the current of blocks k..nb-1."""
    suffix = jnp.flip(jnp.cumsum(jnp.flip(blocks, axis=1), axis=1), axis=1)
    wire = jnp.cumsum(suffix, axis=1) - suffix[:, :1]
    return jnp.clip(1.0 - phys.alpha * wire, 0.0, 1.0)


def nl_ratio(phys: Physics, p):
    """Accumulated-current ratio for p activated LRS cells (the paper's
    piecewise quartic, clamped to its fit domain; 1 for an empty line)."""
    q = jnp.clip(p, 0.0, phys.nl_max)

    def poly(c):
        acc = jnp.full_like(q, c[0])
        for ci in c[1:]:
            acc = acc * q + ci
        return acc

    r = jnp.where(q <= phys.nl_split, poly(phys.nl_lo), poly(phys.nl_hi))
    return jnp.where(p < 0.5, 1.0, r)


def line_current(phys: Physics, effects: Dict, blocks, counts):
    """Per-block currents and LRS counts [P, nb, N] -> (current, count)."""
    if effects["ir_drop"]:
        blocks = blocks * ir_retention(phys, blocks)
    p_total = counts.sum(axis=1)
    if phys.accumulation == "single_shot":
        i = blocks.sum(axis=1)
        if effects["nonlinearity"]:
            i = i * nl_ratio(phys, p_total)
        return i, p_total
    # partial sums: chunks of partial_rows // block blocks accumulate
    # separately, each with its own nonlinearity, then add digitally
    per = max(1, phys.partial_rows // phys.block)
    nb = blocks.shape[1]
    n_chunks = -(-nb // per)
    pad = ((0, 0), (0, n_chunks * per - nb), (0, 0))
    shape = (blocks.shape[0], n_chunks, per, blocks.shape[2])
    i_c = jnp.pad(blocks, pad).reshape(shape).sum(axis=2)
    p_c = jnp.pad(counts, pad).reshape(shape).sum(axis=2)
    if effects["nonlinearity"]:
        i_c = i_c * nl_ratio(phys, p_c)
    return i_c.sum(axis=1), p_total


def sense(phys: Physics, effects: Dict, k_sa, i_pos, i_neg, p_pair):
    """SA decision: 1 iff I+ - I- plus the offset draw is above 0; a pair
    outside the sensing window reads a fair coin."""
    k_off, k_rng = jax.random.split(k_sa)
    diff = i_pos - i_neg
    if effects["sa_variation"]:
        c0, c1, c2 = phys.sa
        sigma = 0.5 * (c0 + c1 * p_pair + c2 * p_pair * p_pair)
        diff = diff + sigma * jax.random.normal(k_off, p_pair.shape)
    out = (diff > 0).astype(jnp.float32)
    if effects["sensing_range"]:
        fail = ((jnp.minimum(i_pos, i_neg) < phys.sense_lo)
                | (jnp.maximum(i_pos, i_neg) > phys.sense_hi))
        coin = jax.random.bernoulli(k_rng, 0.5, out.shape)
        out = jnp.where(fail, coin.astype(jnp.float32), out)
    return out


def crossbar(phys: Physics, effects: Dict, k, x_bits, gp, gn, lead: int,
             products: str):
    """One die's crossbar: x_bits [P, 9*group] -> SA bits [P, group]."""
    ep, en, k_sa = sample_planes(phys, effects, k, gp, gn)
    x_ext = jnp.concatenate(
        [jnp.ones((x_bits.shape[0], lead), jnp.float32), x_bits], axis=1)
    i_pos, p_pos = line_current(phys, effects,
                                block_currents(phys, x_ext, ep, products),
                                block_currents(phys, x_ext, gp, "exact"))
    i_neg, p_neg = line_current(phys, effects,
                                block_currents(phys, x_ext, en, products),
                                block_currents(phys, x_ext, gn, "exact"))
    return sense(phys, effects, k_sa, i_pos, i_neg, p_pos + p_neg)


# ------------------------------------------------------------------ network

def calibrated(phys: Physics, params, images):
    """The weights with batch-norm running statistics from a calibration
    batch: the stem's, and for in-memory BN each block's, on the ideal
    digital forward."""
    params = jax.tree.map(lambda a: a, params)
    x = digital_conv(phys, jnp.asarray(images, jnp.float32), params["stem"], 2)
    bn = dict(params["stem_bn"], mean=x.mean(axis=(0, 1, 2)),
              var=x.var(axis=(0, 1, 2)))
    params["stem_bn"] = bn
    if not phys.use_bn:
        return params
    x = step(bn["gamma"] * (x - bn["mean"]) / jnp.sqrt(bn["var"] + BN_EPS)
             + bn["beta"])
    for name, _, cin, ch, pool in phys.layers():
        if x.shape[-1] < cin:
            x = jnp.concatenate([x] * (cin // x.shape[-1]), axis=-1)
        B, H, W = x.shape[:3]
        wq = quantized(phys, params[name]["w"])
        g = phys.group
        pre = jnp.concatenate(
            [jnp.dot(im2col(x[..., j * g:(j + 1) * g]), wq[..., j],
                     precision=HIGHEST) for j in range(ch // g)], axis=-1)
        pre = pre.reshape(B, H, W, ch)
        p = dict(params[name]["bn"], mean=pre.mean(axis=(0, 1, 2)),
                 var=pre.var(axis=(0, 1, 2)))
        params[name] = dict(params[name], bn=p)
        x = step(jnp.abs(p["gamma"]) * (pre - p["mean"])
                 / jnp.sqrt(p["var"] + BN_EPS) + p["beta"])
        if pool:
            x = maxpool2(x)
    return params


def stem_bits(phys: Physics, params, images):
    """The stem's output bits with the running statistics (inference)."""
    x = digital_conv(phys, jnp.asarray(images, jnp.float32), params["stem"], 2)
    bn = params["stem_bn"]
    return step(bn["gamma"] * (x - bn["mean"]) / jnp.sqrt(bn["var"] + BN_EPS)
                + bn["beta"])


def bn_bias_units(blk, j: int, group: int):
    """In-memory BN folded into bias cells: sign(|gamma| (y - mu) / std +
    beta) = sign(y + beta std / |gamma| - mu)."""
    sl = slice(j * group, (j + 1) * group)
    p = blk["bn"]
    std = jnp.sqrt(p["var"][sl] + BN_EPS)
    return p["beta"][sl] * std / jnp.maximum(jnp.abs(p["gamma"][sl]),
                                             1e-6) - p["mean"][sl]


def layer_planes(phys: Physics, blk, wq, j: int):
    """Group j's placement planes and lead rows from deployed weights."""
    units = bn_bias_units(blk, j, phys.group) if phys.use_bn else None
    return group_planes(phys, wq[..., j], units)


@functools.partial(jax.jit, static_argnames=("phys", "effects", "products"))
def die_predictions(params, x0, key, die, *, phys: Physics, effects: Tuple,
                    products: str = "exact"):
    """Head predictions [B, gh, gw, head_out] of die `die` of the
    population keyed `key`, from the stem's bits x0 [B, H, W, group]."""
    eff = dict(effects)
    x = x0
    g = phys.group
    for name, layer_id, cin, ch, pool in phys.layers():
        if x.shape[-1] < cin:
            x = jnp.concatenate([x] * (cin // x.shape[-1]), axis=-1)
        B, H, W = x.shape[:3]
        wq = quantized(phys, params[name]["w"])
        outs = []
        for j in range(ch // g):
            gp, gn, lead = layer_planes(phys, params[name], wq, j)
            bits = crossbar(phys, eff, die_key(key, die, layer_id, j),
                            im2col(x[..., j * g:(j + 1) * g]), gp, gn, lead,
                            products)
            outs.append(bits.reshape(B, H, W, g))
        x = jnp.concatenate(outs, axis=-1)
        if pool:
            x = maxpool2(x)
    return digital_dense(phys, x, params["head"]) + params["head_b"]


# ------------------------------------------------------------------ training
# Quantization-aware training against a population of dies (the paper's
# Sec. V at population scale): each step draws `chips` dies, adds each
# die's frozen device-variation error and a fresh SA-offset draw to the
# ideal pre-activation, and averages the YOLO loss over dies and images.
# Hard steps pass gradients straight through: the activation's inside
# |x| <= 1 (slope 1/2), the weight quantizer's inside |w| <= 1.

def ste(hard, soft):
    return soft + jax.lax.stop_gradient(hard - soft)


def step_ste(x):
    return ste(step(x), jnp.clip(0.5 * (x + 1.0), 0.0, 1.0))


def quantized_ste(phys: Physics, w):
    return ste(quantized(phys, jax.lax.stop_gradient(w)),
               jnp.clip(w, -1.0, 1.0))


def deviation(phys: Physics, effects: Dict, k, x_bits, gp, gn, lead: int,
              products: str):
    """One die's frozen variation error of one group: the current
    difference its effective planes add to the nominal ones."""
    ep, en, _ = sample_planes(phys, effects, k, gp, gn)
    ep = ep - (gp + (1.0 - gp) * phys.leak)
    en = en - (gn + (1.0 - gn) * phys.leak)
    x_ext = jnp.concatenate(
        [jnp.ones((x_bits.shape[0], lead), jnp.float32), x_bits], axis=1)
    return (block_currents(phys, x_ext, ep, products).sum(axis=1)
            - block_currents(phys, x_ext, en, products).sum(axis=1))


def train_predictions(params, images, key, ens_key, *, phys: Physics,
                      effects: Dict, chips: int, products: str):
    """[chips, B, gh, gw, head_out] training predictions."""
    g = phys.group
    x = digital_conv(phys, images, params["stem"], 2)
    bn = params["stem_bn"]
    x = step_ste(bn["gamma"] * (x - x.mean(axis=(0, 1, 2)))
                 / jnp.sqrt(x.var(axis=(0, 1, 2)) + BN_EPS) + bn["beta"])
    for name, layer_id, cin, ch, pool in phys.layers():
        if x.shape[-1] < cin:
            x = jnp.concatenate([x] * (cin // x.shape[-1]), axis=-1)
        blk = params[name]
        wq = quantized_ste(phys, blk["w"])
        xf = x.reshape((-1,) + x.shape[-3:])
        pre = jnp.concatenate(
            [digital_conv(phys, xf[..., j * g:(j + 1) * g],
                          wq[..., j].reshape(3, 3, g, g), 1)
             for j in range(ch // g)], axis=-1)
        if phys.use_bn:
            p = blk["bn"]
            pre = (jnp.abs(p["gamma"]) * (pre - pre.mean(axis=(0, 1, 2)))
                   / jnp.sqrt(pre.var(axis=(0, 1, 2)) + BN_EPS) + p["beta"])
        pre = pre.reshape(x.shape[:-1] + (ch,))
        bits = jax.lax.stop_gradient(x)
        if effects["device_variation"]:
            wd = jax.lax.stop_gradient(wq)
            dev = []
            for c in range(chips):
                xc = bits[c] if bits.ndim == 5 else bits
                B, H, W = xc.shape[:3]
                dev.append(jnp.concatenate([
                    deviation(phys, effects,
                              die_key(ens_key, jnp.uint32(c), layer_id, j),
                              im2col(xc[..., j * g:(j + 1) * g]),
                              *layer_planes(phys, blk, wd, j), products
                              ).reshape(B, H, W, g)
                    for j in range(ch // g)], axis=-1))
            pre = pre + jnp.stack(dev)
        if pre.ndim == 4:
            pre = jnp.broadcast_to(pre[None], (chips,) + pre.shape)
        if effects["sa_variation"]:
            lrs = jnp.mean(jnp.abs(jax.lax.stop_gradient(wq)))
            p = bits.sum(axis=-1, keepdims=True) * lrs * 9.0 / cin * g
            c0, c1, c2 = phys.sa
            k = jax.random.fold_in(key, layer_id)
            eps = jnp.stack([jax.random.normal(jax.random.fold_in(
                k, jnp.uint32(c)), pre.shape[1:]) for c in range(chips)])
            pre = pre + 0.5 * (c0 + c1 * p + c2 * p * p) * eps
        x = step_ste(pre)
        if pool:
            x = maxpool2(x)
    return digital_dense(phys, x, params["head"]) + params["head_b"]


def yolo_loss(phys: Physics, pred, targets):
    """YOLOv2 loss summed over cells and divided by the responsible
    anchors: 5 x (centre + sqrt-size error), objectness BCE (0.5 on
    background), class cross-entropy."""
    A, C = phys.n_anchors, phys.n_classes
    p = pred.reshape(pred.shape[:-1] + (A, 5 + C))
    obj_t, xywh, cls_t = targets["obj"], targets["txywh"], targets["cls"]
    anchors = jnp.asarray(ANCHORS[:A])
    wh = anchors * jnp.exp(jnp.clip(p[..., 2:4], -4.0, 4.0))
    xy_err = jnp.sum(jnp.square(jax.nn.sigmoid(p[..., 0:2])
                                - xywh[..., 0:2]), -1)
    wh_err = jnp.sum(jnp.square(jnp.sqrt(wh + 1e-9)
                                - jnp.sqrt(xywh[..., 2:4] + 1e-9)), -1)
    coord = 5.0 * jnp.sum(obj_t * (xy_err + wh_err))
    o = p[..., 4]
    bce = jnp.maximum(o, 0) - o * obj_t + jnp.log1p(jnp.exp(-jnp.abs(o)))
    obj = jnp.sum(obj_t * bce) + 0.5 * jnp.sum((1 - obj_t) * bce)
    logp = jax.nn.log_softmax(p[..., 5:], axis=-1)
    nll = -jnp.take_along_axis(logp, cls_t[..., None], axis=-1)[..., 0]
    return (coord + obj + jnp.sum(obj_t * nll)) / jnp.maximum(
        jnp.sum(obj_t), 1.0)


def adamw(grads, state, params, lr, opt: Tuple):
    """AdamW with global-norm clipping; state = (m, v, step)."""
    o = dict(opt)
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(norm, 1e-9))
    m, v, t = state
    t = t + 1
    c1 = 1.0 - o["b1"] ** t.astype(jnp.float32)
    c2 = 1.0 - o["b2"] ** t.astype(jnp.float32)
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b, v, g)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + o["eps"])
                                  + o["weight_decay"] * p), params, m, v)
    return params, (m, v, t)


@functools.partial(jax.jit, static_argnames=("phys", "effects", "chips",
                                             "opt", "products"))
def train_step(params, state, images, targets, lr, key, ens_key, *,
               phys: Physics, effects: Tuple, chips: int, opt: Tuple,
               products: str = "exact"):
    """One QAT step: (params, (m, v, step), loss)."""
    def loss_fn(p):
        pred = train_predictions(p, images, key, ens_key, phys=phys,
                                 effects=dict(effects), chips=chips,
                                 products=products)
        pred = pred.reshape((-1,) + pred.shape[2:])
        tiled = jax.tree.map(
            lambda t: jnp.tile(t, (chips,) + (1,) * (t.ndim - 1)), targets)
        return yolo_loss(phys, pred, tiled)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    params, state = adamw(grads, state, params, lr, opt)
    return params, state, loss


def adamw_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return (zeros, zeros, jnp.zeros((), jnp.int32))


def effects_tuple(effects: Dict) -> Tuple:
    return tuple(sorted(effects.items()))


# ------------------------------------------------------------------ compare

# A head output moves by |w| ~ 1/sqrt(channels) when one input bit of its
# cell differs; summing the same bits in another order moves it by < 1e-5.
CELL_TOL = 1e-3


def cells_off(pred, ref) -> float:
    """Share of head grid cells whose predictions differ from the
    reference's by more than CELL_TOL in any output."""
    d = np.abs(np.asarray(pred, np.float64) - np.asarray(ref, np.float64))
    return float(np.mean(d.max(axis=-1) > CELL_TOL))
