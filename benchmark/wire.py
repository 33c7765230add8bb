"""The gRPC wire contract as a load client speaks it: the generated
protobuf messages of ``ory.keto.acl.v1alpha1`` and the method paths, on a
plain grpc channel. Imports no JAX and nothing of the server, so client
processes stay off the chip.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GEN = os.path.join(ROOT, "keto_tpu", "api", "gen")
if _GEN not in sys.path:
    sys.path.append(_GEN)

from ory.keto.acl.v1alpha1 import check_service_pb2  # noqa: E402

PKG = "/ory.keto.acl.v1alpha1."
BATCH_CHECK = PKG + "CheckService/BatchCheck"
MAX_MESSAGE = 64 << 20


def channel(port: int):
    import grpc

    return grpc.insecure_channel(
        f"127.0.0.1:{port}",
        options=[
            ("grpc.max_send_message_length", MAX_MESSAGE),
            ("grpc.max_receive_message_length", MAX_MESSAGE),
        ],
    )


def method(ch, path: str):
    """A unary call on ``ch`` that takes and returns serialized bytes."""
    return ch.unary_unary(path)


def batch_check_request(objs, subject_ids) -> bytes:
    """Columnar BatchCheck of ``objs[i]#...@subject_ids[i]``."""
    return check_service_pb2.BatchCheckRequest(
        namespaces=[o[0] for o in objs],
        objects=[o[1] for o in objs],
        relations=[o[2] for o in objs],
        subject_ids=list(subject_ids),
    ).SerializeToString()


def batch_allowed(resp: bytes) -> list:
    return list(check_service_pb2.BatchCheckResponse.FromString(resp).allowed)
