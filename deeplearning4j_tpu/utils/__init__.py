"""Host-side utilities (reference: deeplearning4j-core util/ — ModelSerializer,
ImageLoader, ArchiveUtils, DiskBasedQueue, StringGrid, MathUtils)."""

from deeplearning4j_tpu.utils.archive import unzip_file_to  # noqa: F401
from deeplearning4j_tpu.utils.diskqueue import DiskBasedQueue  # noqa: F401
from deeplearning4j_tpu.utils.stringgrid import StringGrid  # noqa: F401
from deeplearning4j_tpu.utils.image import (  # noqa: F401
    as_matrix,
    as_row_vector,
    decode_png,
    load_image,
    resize,
    save_pgm,
)


def __getattr__(name):
    # lazily (PEP 562): the serializer pulls in jax, and the control plane
    # (monitor.flight -> utils.fileio) must import this package without it
    if name == "ModelSerializer":
        from deeplearning4j_tpu.utils.serializer import ModelSerializer

        return ModelSerializer
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
