"""The program's float32 twins of the image classifiers, for
``test_reference.py``: a tiny file's ``reference_case.program_cells`` names
one of these (``"<module>:<callable>"``, called with the case's model)."""


def amoebanetd_cells(model):
    from mpi4dl_tpu.models.amoebanet import amoebanetd

    return amoebanetd(num_layers=model["num_layers"], num_filters=model["num_filters"],
                      num_classes=model["num_classes"])


def resnet_v2_cells(model):
    from mpi4dl_tpu.models.resnet import get_resnet_v2

    return get_resnet_v2(depth=model["depth"], num_classes=model["num_classes"],
                         pool_kernel=model["image_size"] // 4)
