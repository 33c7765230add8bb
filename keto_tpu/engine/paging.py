"""Shared continuation-token mint/validate for paged read surfaces.

Expand paging (engine/expand.py host walk, SnapshotExpandEngine's CSR walk)
and list paging (engine/listing.py) all cut version-pinned cursors with the
same failure contract:

- garbage / truncated / non-JSON token        -> ErrMalformedPageToken (400)
- token minted by a different engine flavor   -> ErrMalformedPageToken (400)
- token pinned to a superseded data version   -> ErrStalePageToken (409)

The cursor is base64url(compact-JSON) of ``{"k": kind, "v": version, ...}``
plus engine-specific payload keys. Keeping the mint/validate pair here (one
wire format, one error scheme) is what lets a list token presented to the expand
endpoint — or vice versa — fail typed instead of resuming garbage work.
"""

from __future__ import annotations

import base64
import json

from ..utils.errors import ErrMalformedPageToken, ErrStalePageToken


def encode_page_token(kind: str, version, payload: dict) -> str:
    """Mint a continuation cursor: ``payload`` keys ride next to the
    ``k``/``v`` pin (they must not collide with those two names)."""
    doc = {"k": kind, "v": version, **payload}
    raw = json.dumps(doc, separators=(",", ":")).encode()
    return base64.urlsafe_b64encode(raw).decode()


def decode_page_token(
    token: str, kind: str, version, what: str = "page"
) -> dict:
    """Validate and open a cursor -> the full payload dict.

    Raises ErrMalformedPageToken on garbage or a kind (engine-flavor)
    mismatch, ErrStalePageToken when the pinned version no longer matches
    ``version``. ``what`` names the surface in error text ("expand page",
    "list page")."""
    try:
        payload = json.loads(base64.urlsafe_b64decode(token.encode()))
        got_kind = payload["k"]
        got_version = payload["v"]
    except Exception as e:
        raise ErrMalformedPageToken(f"malformed {what} token") from e
    if got_kind != kind:
        raise ErrMalformedPageToken(
            f"{what} token was issued by a {got_kind!r} engine"
        )
    if got_version != version:
        raise ErrStalePageToken(
            f"{what} token expired: issued at version {got_version}, "
            f"serving {version}"
        )
    return payload
