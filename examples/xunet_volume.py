"""X-UNet3D (paper SVI): halo-partitioned volumetric prediction.

Trains a reduced 3D UNet with attention gates on the analytic volume-flow
proxy, its inputs featurized from each car's sampled surface as the server
does (``repro.graphx.volume``), then runs inference BOTH on the full domain
and partitioned into halo-extended slabs — and shows the outputs agree to
float tolerance while each slab touches only a fraction of the domain.

Run:  PYTHONPATH=src python examples/xunet_volume.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import unet_halo
from repro.core.graph_build import sample_surface
from repro.data import geometry as geo
from repro.graphx import volume
from repro.models import xunet3d
from repro.optim.adam import AdamConfig, adam_init, adam_update


def make_batch(cfg, sample_id, n_points=2048):
    """Voxel features of one car from its sampled surface (the serving
    featurizer, ``repro.graphx.volume``) and the analytic flow targets."""
    params = geo.sample_params(sample_id)
    verts, faces = geo.car_surface(params)
    pts, nrm = sample_surface(verts, faces, n_points,
                              np.random.default_rng(sample_id))
    spec = volume.calibrate_spec(pts, cfg, n_points)
    sdf = volume.sdf_grid(jnp.asarray(pts), jnp.asarray(nrm), n_points, spec)
    feats = volume.slab_features(sdf, 0, cfg.grid[0], spec)
    centres = np.stack(np.meshgrid(*volume.voxel_axes(spec), indexing="ij"),
                       -1).reshape(-1, 3)
    targets = geo.volume_fields(centres.astype(np.float64), params)
    return {"inputs": feats,
            "targets": jnp.asarray(targets.reshape(1, *cfg.grid,
                                                   cfg.out_channels),
                                   jnp.float32)}


def main():
    cfg = get_config("xunet3d-drivaer").reduced()
    params = xunet3d.init(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamConfig(lr_max=1.5e-4, lr_min=5e-7, total_steps=30)
    opt = adam_init(params)

    @jax.jit
    def step(params, opt, batch):
        loss, g = jax.value_and_grad(
            lambda p: xunet3d.train_loss(p, cfg, batch, 0.05))(params)
        params, opt, _ = adam_update(opt_cfg, g, opt, params)
        return params, opt, loss

    batches = [make_batch(cfg, i) for i in range(3)]
    for it in range(30):
        params, opt, loss = step(params, opt, batches[it % 3])
        if it % 10 == 0:
            print(f"step {it}: loss {float(loss):.5f}")

    apply_fn = lambda x: xunet3d.apply(params, cfg, x)
    x = batches[0]["inputs"]
    full = apply_fn(x)
    align = 2 ** (cfg.depth - 1)
    rf = xunet3d.receptive_field(cfg)
    halo = -(-rf // align) * align
    part = unet_halo.apply_partitioned(apply_fn, x, 4, halo,
                                       axis=1, align=align)
    print(f"receptive field={rf} voxels -> halo={halo}; "
          f"partitioned-vs-full max diff: "
          f"{float(jnp.max(jnp.abs(part - full))):.2e}")


if __name__ == "__main__":
    main()
