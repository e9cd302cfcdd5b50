"""Plain NumPy reference of the supergraph drawing.

The scene is fitted into the image with a blank margin (uniform scale, y
up). Live supernodes are disks of
radius √size (in pixels: clipped to [1, 1/8 of the image]) covering every
integer pixel ``(x, y)`` with ``(x − cx)² + (y − cy)² ≤ r²``. Each
superedge is drawn as 8 samples at ``t = (k + ½)/8`` along the segment,
each adding the edge's multiplicity (rounded, at least 1) to the pixel it
falls in, in the colour group of its nearer endpoint; samples outside the
image are dropped. Both layers count per colour group; a layer's colour
is the palette mixed by ``log1p(gain·count)`` and its opacity
``1 − exp(−Σ log1p)``; edges (opacity × 0.85) go under nodes, over a
white background, then each channel is rounded into [0, 255].
"""
from __future__ import annotations

import numpy as np

PALETTE = np.array([
    [140, 86, 75], [197, 176, 213], [148, 103, 189], [255, 187, 120],
    [255, 127, 14], [255, 152, 150], [214, 39, 40], [152, 223, 138],
    [44, 160, 44], [174, 199, 232], [31, 119, 180],
], np.float32)
SAMPLES = 8
MAX_INC = 1 << 20


def transform(pos, alive, width, height, margin=0.04):
    """(scale, ox, oy) mapping world coordinates to pixels."""
    p = pos[alive] if alive.any() else pos
    lo, hi = p.min(0), p.max(0)
    span = np.maximum(hi - lo, np.float32(1e-6))
    scale = (1.0 - 2.0 * margin) * min(width / span[0], height / span[1])
    c = (lo + hi) / 2.0
    return float(scale), float(c[0]), float(c[1])


def _disks(px, py, r, groups, n_groups, h, w):
    acc = np.zeros((n_groups, h, w), np.int64)
    live = np.nonzero(r > 0)[0]
    half = np.ceil(r[live]).astype(np.int64) + 1
    for b in np.unique(half):
        idx = live[half == b]
        off = np.arange(-b, b + 1)
        xs = np.floor(px[idx]).astype(np.int64)[:, None] + off[None, :]
        ys = np.floor(py[idx]).astype(np.int64)[:, None] + off[None, :]
        dx2 = (xs.astype(np.float32) - px[idx, None]) ** 2
        dy2 = (ys.astype(np.float32) - py[idx, None]) ** 2
        inside = dy2[:, :, None] + dx2[:, None, :] <= (r[idx] ** 2)[:, None, None]
        inside &= ((ys >= 0) & (ys < h))[:, :, None] & ((xs >= 0) & (xs < w))[:, None, :]
        k, iy, ix = np.nonzero(inside)
        np.add.at(acc, (groups[idx][k], ys[k, iy], xs[k, ix]), 1)
    return acc


def _edges(px, py, groups, pairs, weights, n_groups, h, w):
    u, v = pairs[:, 0], pairs[:, 1]
    t = ((np.arange(SAMPLES, dtype=np.float32) + 0.5) / SAMPLES).astype(px.dtype)
    sx = px[u][:, None] + t[None, :] * (px[v] - px[u])[:, None]
    sy = py[u][:, None] + t[None, :] * (py[v] - py[u])[:, None]
    ix = np.floor(sx.astype(np.float32)).astype(np.int64)
    iy = np.floor(sy.astype(np.float32)).astype(np.int64)
    g = np.where(t[None, :].astype(np.float32) < 0.5, groups[u][:, None],
                 groups[v][:, None])
    inc = np.broadcast_to(
        np.clip(np.round(weights), 1, MAX_INC)[:, None], ix.shape)
    ok = (ix >= 0) & (ix < w) & (iy >= 0) & (iy < h)
    flat = (g[ok] * h + iy[ok]) * w + ix[ok]
    acc = np.bincount(flat, weights=inc[ok], minlength=n_groups * h * w)
    return acc.reshape(n_groups, h, w)


def _layer(acc, gain):
    i = np.log1p(np.float32(gain) * acc.astype(np.float32))
    tot = i.sum(0)
    rgb = np.einsum("ghw,gc->hwc", i, PALETTE) / np.maximum(tot, 1e-9)[..., None]
    return rgb, 1.0 - np.exp(-tot)


def render(positions, sizes, groups, pairs, weights, width=1024, height=1024,
           margin=0.04, node_gain=4.0, edge_gain=1.0, edge_alpha=0.85,
           min_radius=1.0, max_radius_frac=0.125):
    """[H, W, 3] uint8 image of the scene. Edges and disks that cannot
    reach the image are skipped before drawing; they would draw nothing."""
    pos = np.asarray(positions, np.float32)
    radii = np.sqrt(np.maximum(np.asarray(sizes, np.float32), 0))
    groups = np.asarray(groups, np.int64)
    alive = radii > 0
    scale, ox, oy = transform(pos, alive, width, height, margin)
    px = ((pos[:, 0] - ox) * scale + width / 2.0).astype(np.float32)
    py = (height / 2.0 - (pos[:, 1] - oy) * scale).astype(np.float32)
    r = np.where(alive, np.clip(radii * scale, min_radius,
                                max_radius_frac * min(height, width)), 0)
    r = r.astype(np.float32)
    r = np.where((px + r >= -1) & (px - r <= width + 1)
                 & (py + r >= -1) & (py - r <= height + 1), r, 0)
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    weights = np.asarray(weights, np.float64)
    u, v = pairs[:, 0], pairs[:, 1]
    reach = ((np.maximum(px[u], px[v]) >= -1) & (np.minimum(px[u], px[v]) <= width + 1)
             & (np.maximum(py[u], py[v]) >= -1) & (np.minimum(py[u], py[v]) <= height + 1))
    pairs, weights = pairs[reach], weights[reach]
    g = len(PALETTE)
    img = np.broadcast_to(np.float32(255), (height, width, 3))
    if len(pairs):
        rgb, a = _layer(_edges(px, py, groups, pairs, weights, g, height, width),
                        edge_gain)
        a = (edge_alpha * a)[..., None]
        img = a * rgb + (1 - a) * img
    if (r > 0).any():
        rgb, a = _layer(_disks(px, py, r, groups, g, height, width), node_gain)
        img = a[..., None] * rgb + (1 - a[..., None]) * img
    return np.clip(np.round(img), 0, 255).astype(np.uint8)


def block_means(image: np.ndarray, block: int) -> np.ndarray:
    h, w, c = image.shape
    return image.reshape(h // block, block, w // block, block, c).astype(
        np.float64).mean(axis=(1, 3))
