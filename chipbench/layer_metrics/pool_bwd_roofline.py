"""Share of its roofline the pool backward kernel reaches: the least bytes
the model's stride-1 3x3 max-pool backwards must move on one chip (read
the input and the output's cotangent, write the input's cotangent, bf16;
shapes from the reference's forward pass, a chip's share under spatial
parallelism, halos not counted) / the chip's peak bytes/s / the kernel's
measured time. The kernel does no matrix work: it is bound by memory
bandwidth, so the bytes are the roofline."""

from chipbench.harness import counting, xtrace

KERNEL = "mpi4dl_pool_bwd"


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    session, trainer = context["session"], context["trainer"]
    tiles = trainer.mesh.devices.size if trainer.n_spatial else 1
    least = counting.stride1_max_pool_bytes(
        session.ref_cells, session.x_shape, session.x_dtype, 2)
    return 100.0 * (least / tiles / context["peaks"]["hbm_bytes_per_s"]) / seconds
