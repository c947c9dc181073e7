"""halo_share.volume: share of the voxels the U-Net computed that were halo,
1 - owned / computed (ServerStats ``volume_voxels_owned`` and
``volume_voxels_computed`` over the window's answers; program counters)."""


def read(ctx):
    v = ctx["run"].get("voxels")
    if not v or not v["computed"]:
        return None
    return 100.0 * (1.0 - v["owned"] / v["computed"])
