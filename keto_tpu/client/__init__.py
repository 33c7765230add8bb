"""Client SDK: typed Python clients for the keto_tpu REST and gRPC APIs.

The reference ships a go-swagger generated REST SDK (reference
internal/httpclient/, ~6.4k LoC, regenerated by `make sdk`) consumed by its
e2e suite, plus published gRPC stubs. This package is the keto_tpu
equivalent, written against spec/api.json rather than generated from it:

- ``RestClient`` — the full REST surface (check, batch check, expand,
  relation-tuple CRUD with pagination, health, version, metrics), returning
  domain objects (RelationTuple, Tree) and raising the same KetoError
  classes the server maps from.
- ``GrpcClient`` — the five ory.keto.acl.v1alpha1 services over one read
  and one write channel, same surface as the published Keto gRPC clients.

Both speak to read and write planes separately (reference
cmd/client/grpc_client.go reads remotes from flags/env the same way the
CLI here does).
"""

from __future__ import annotations

import json

from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

from ..engine.tree import Tree
from ..telemetry.tracing import (
    HEDGE_HEADER,
    TRACEPARENT_HEADER,
    current_traceparent,
    mint_traceparent,
)
from ..relationtuple.definitions import (
    RelationQuery,
    RelationTuple,
    Subject,
    SubjectID,
    SubjectSet,
)
from ..utils.errors import (
    ErrForbidden,
    ErrInternal,
    ErrMalformedInput,
    ErrNotFound,
    ErrReadOnlyFollower,
    ErrResourceExhausted,
    ErrStalePageToken,
    ErrUnavailable,
    KetoError,
)
from .hedge import EndpointRouter, HedgePolicy, Hedger
from .retry import (
    RETRYABLE_HTTP_STATUS,
    RetryBudget,
    RetryPolicy,
    grpc_retryable,
    run_with_retry,
)
from .vocabcache import VocabCache

#: REST header / gRPC metadata key carrying the overload brownout
#: ladder's criticality class (critical | default | sheddable); the
#: server spellings live in api/rest.py and api/services.py but the
#: client must not import aiohttp/grpc server modules to name them
CRITICALITY_HEADER = "X-Request-Criticality"
CRITICALITY_METADATA_KEY = "x-keto-criticality"

__all__ = [
    "RestClient",
    "GrpcClient",
    "ReplicatedRestClient",
    "CheckResult",
    "TuplePage",
    "ListResult",
    "RetryPolicy",
    "RetryBudget",
    "HedgePolicy",
    "Hedger",
    "EndpointRouter",
    "VocabCache",
]


def _snaptoken_version(snaptoken: str) -> int:
    """Snaptoken -> minimum store version for the encoded wire frame
    (which carries the version number itself, not the token string)."""
    if not snaptoken:
        return 0
    from ..replication.token import parse_snaptoken

    try:
        return parse_snaptoken(snaptoken).version
    except ValueError:
        return 0


@dataclass(frozen=True)
class CheckResult:
    allowed: bool
    snaptoken: str = ""
    # the W3C traceparent this check was sent under — the client mints one
    # per request, so callers can correlate with server-side spans, flight
    # records, and exemplars by its trace id
    traceparent: str = ""


def _trace_headers(traceparent: Optional[str], hedge: bool) -> tuple[str, dict]:
    """(traceparent_used, headers) for one check attempt: reuse the given
    traceparent (hedged duplicate), else continue any active span's trace,
    else mint a fresh root. Duplicates carry ``x-keto-hedge: 1``."""
    tp = traceparent or current_traceparent() or mint_traceparent()
    headers = {TRACEPARENT_HEADER: tp}
    if hedge:
        headers[HEDGE_HEADER] = "1"
    return tp, headers


@dataclass(frozen=True)
class TuplePage:
    relation_tuples: list[RelationTuple]
    next_page_token: str


@dataclass(frozen=True)
class ListResult:
    """One page of a list-objects / list-subjects answer: sorted object
    names or subject-id strings, a continuation token ("" on the last
    page), and the snaptoken the page was served at (chain it into later
    reads for at-least-as-fresh consistency)."""

    items: list[str]
    next_page_token: str = ""
    snaptoken: str = ""


def _error_for(status_code: int, body: dict, headers=None) -> KetoError:
    err = body.get("error") or {}
    message = err.get("message", "")
    details = err.get("details") or {}
    hint = details.get("leader_hint")
    if status_code == 503 and isinstance(hint, dict):
        # a read-only follower (or fenced ex-leader) told us who leads:
        # surface the typed error so the write path can follow it
        return ErrReadOnlyFollower(message or None, leader_hint=hint)
    cls = {
        400: ErrMalformedInput,
        403: ErrForbidden,
        404: ErrNotFound,
        409: ErrStalePageToken,
        429: ErrResourceExhausted,
        503: ErrUnavailable,
    }.get(status_code, ErrInternal)
    e = cls(message or None)
    if headers is not None:
        # surface the server's Retry-After hint so run_with_retry floors
        # its backoff on it (the server asked for at least that quiet)
        ra = headers.get("Retry-After") or headers.get("retry-after")
        if ra is not None:
            try:
                e.retry_after_s = max(0.0, float(ra))
            except (TypeError, ValueError):
                pass
    return e


def _subject_params(subject: Subject, prefix: str = "") -> dict:
    if isinstance(subject, SubjectID):
        return {f"{prefix}subject_id": subject.id}
    return {
        f"{prefix}subject_set.namespace": subject.namespace,
        f"{prefix}subject_set.object": subject.object,
        f"{prefix}subject_set.relation": subject.relation,
    }


class RestClient:
    """The REST surface over httpx. ``read_url``/``write_url`` like
    ``http://127.0.0.1:4466`` (no trailing slash needed)."""

    def __init__(
        self,
        read_url: str,
        write_url: Optional[str] = None,
        timeout: float = 30.0,
        verify=True,
        retry: Optional[RetryPolicy] = None,
        transport=None,  # httpx transport override (tests: MockTransport)
        retry_budget: Optional[RetryBudget] = None,
        criticality: Optional[str] = None,  # default shed class for checks
    ):
        import httpx

        self.read_url = read_url.rstrip("/")
        self.write_url = (write_url or read_url).rstrip("/")
        self.timeout = timeout
        self.retry = RetryPolicy() if retry is None else retry
        # shared across every call of this instance: retries are capped
        # at ~10% of request volume so a sustained shed cannot be
        # amplified into a retry storm (engine/overload.py is the server
        # half of this discipline)
        self.retry_budget = (
            RetryBudget() if retry_budget is None else retry_budget
        )
        self.criticality = criticality
        self._http = httpx.Client(
            timeout=timeout, verify=verify, transport=transport
        )

    def close(self) -> None:
        self._http.close()

    def __enter__(self) -> "RestClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- internals -----------------------------------------------------------

    def _request(self, method: str, url: str, ok=(200,), **kw):
        import httpx

        # Retry policy: 429/503 responses are shed-before-work signals and
        # safe to retry for ANY method; transport-level failures (refused
        # connection around a replica restart) are only safely retried for
        # reads — a write whose response was lost may have been applied.
        read_method = method.upper() in ("GET", "HEAD")

        def retryable(e: BaseException) -> bool:
            if isinstance(e, (ErrResourceExhausted, ErrUnavailable)):
                return True
            return read_method and isinstance(e, httpx.TransportError)

        def attempt(_remaining):
            resp = self._http.request(method, url, **kw)
            if resp.status_code not in ok:
                try:
                    body = resp.json()
                except ValueError:
                    body = {}
                raise _error_for(resp.status_code, body, resp.headers)
            return resp

        return run_with_retry(
            attempt, self.retry, retryable, timeout=self.timeout,
            budget=self.retry_budget,
        )

    # -- read plane ------------------------------------------------------------

    def check(
        self,
        tuple_or_str: RelationTuple | str,
        max_depth: int = 0,
        snaptoken: str = "",
        latest: bool = False,
        traceparent: Optional[str] = None,
        hedge: bool = False,
        criticality: Optional[str] = None,
    ) -> CheckResult:
        """200 and 403 are both answers (allowed true/false); other codes
        raise. `snaptoken`/`latest` request at-least-as-fresh evaluation
        (the Zanzibar zookie contract). Every check carries a W3C
        ``traceparent`` header (minted here unless supplied) so server-side
        spans join the client's trace; ``hedge`` tags the request as a
        hedged duplicate. ``criticality`` (or the client-level default)
        tags the check's shed class for the server's brownout ladder."""
        t = (
            RelationTuple.from_string(tuple_or_str)
            if isinstance(tuple_or_str, str)
            else tuple_or_str
        )
        params = {
            "namespace": t.namespace,
            "object": t.object,
            "relation": t.relation,
            **_subject_params(t.subject),
        }
        if max_depth:
            params["max-depth"] = str(max_depth)
        if snaptoken:
            params["snaptoken"] = snaptoken
        if latest:
            params["latest"] = "true"
        tp, headers = _trace_headers(traceparent, hedge)
        crit = criticality or self.criticality
        if crit:
            headers[CRITICALITY_HEADER] = crit
        resp = self._request(
            "GET",
            f"{self.read_url}/check",
            ok=(200, 403),
            params=params,
            headers=headers,
        )
        return CheckResult(
            allowed=bool(resp.json().get("allowed")), traceparent=tp
        )

    def check_hedged(
        self,
        tuple_or_str: RelationTuple | str,
        hedger: Hedger,
        max_depth: int = 0,
        snaptoken: str = "",
        latest: bool = False,
    ):
        """One hedged check through ``hedger``: the primary and any fired
        duplicate share ONE traceparent (same trace id server-side), and
        the duplicate alone carries ``x-keto-hedge: 1`` — so the operator
        can tell the reissue from the original in spans and flight
        records. Returns the hedger's HedgedCall (``.result`` is the
        CheckResult)."""
        tp = current_traceparent() or mint_traceparent()

        def attempt(is_hedge: bool):
            return self.check(
                tuple_or_str,
                max_depth,
                snaptoken,
                latest,
                traceparent=tp,
                hedge=is_hedge,
            )

        return hedger.call(
            lambda: attempt(False), hedge=lambda: attempt(True)
        )

    def batch_check(
        self,
        tuples: Sequence[RelationTuple | str],
        max_depth: int = 0,
        snaptoken: str = "",
        latest: bool = False,
        traceparent: Optional[str] = None,
        criticality: Optional[str] = None,
    ) -> list[bool]:
        """The /check/batch transport: many checks, one request.
        `snaptoken`/`latest` apply at-least-as-fresh consistency to the
        whole batch."""
        body = {
            "tuples": [
                (
                    RelationTuple.from_string(t)
                    if isinstance(t, str)
                    else t
                ).to_dict()
                for t in tuples
            ]
        }
        if max_depth:
            body["max_depth"] = max_depth
        params = {}
        if snaptoken:
            params["snaptoken"] = snaptoken
        if latest:
            params["latest"] = "true"
        _, headers = _trace_headers(traceparent, hedge=False)
        crit = criticality or self.criticality
        if crit:
            headers[CRITICALITY_HEADER] = crit
        resp = self._request(
            "POST",
            f"{self.read_url}/check/batch",
            json=body,
            params=params,
            headers=headers,
        )
        return [bool(v) for v in resp.json()["allowed"]]

    def batch_check_encoded(
        self,
        cache: VocabCache,
        tuples: Sequence[RelationTuple | str],
        snaptoken: str = "",
        traceparent: Optional[str] = None,
        max_resyncs: int = 2,
    ) -> list[bool]:
        """The id-native transport: tuples are vocab-encoded locally
        against ``cache`` and shipped as packed int32 columns (``POST
        /check/batch-encoded``) — no per-tuple strings on the wire, no
        vocab probes server-side. A write landing between encode and
        send bumps the server's vocab epoch; the typed 409 bounce makes
        the cache re-sync and the batch is re-encoded and re-sent (at
        most ``max_resyncs`` times)."""
        from ..api import wirecodec

        mv = _snaptoken_version(snaptoken)
        tp, headers = _trace_headers(traceparent, hedge=False)
        headers["Content-Type"] = "application/octet-stream"
        for attempt in range(max_resyncs + 1):
            start, target, ns = cache.encode(tuples)
            frame = wirecodec.encode_check_request(
                start,
                target,
                lineage=cache.lineage,
                epoch=cache.epoch,
                ns=ns,
                min_version=mv,
                traceparent=tp,
            )
            resp = self._http.post(
                f"{self.read_url}/check/batch-encoded",
                content=frame,
                headers=headers,
            )
            if resp.status_code == 200:
                allowed, _tok = wirecodec.decode_check_response(
                    resp.content
                )
                return [bool(v) for v in allowed]
            if resp.status_code == 409 and attempt < max_resyncs:
                cache.sync()
                continue
            try:
                body = resp.json()
            except ValueError:
                body = {}
            raise _error_for(resp.status_code, body)
        raise ErrUnavailable("encoded batch check exhausted resyncs")

    def vocab_cache(self, **kw) -> VocabCache:
        """A VocabCache sharing this client's connection pool."""
        return VocabCache(self.read_url, http=self._http, **kw)

    def expand(
        self, subject_set: SubjectSet, max_depth: int = 0
    ) -> Optional[Tree]:
        params = {
            "namespace": subject_set.namespace,
            "object": subject_set.object,
            "relation": subject_set.relation,
        }
        if max_depth:
            params["max-depth"] = str(max_depth)
        resp = self._request(
            "GET", f"{self.read_url}/expand", params=params
        )
        doc = resp.json()
        return None if doc is None else Tree.from_dict(doc)

    @staticmethod
    def _list_params(
        base: dict, max_depth: int, page_size: int, page_token: str,
        snaptoken: str, latest: bool,
    ) -> dict:
        if max_depth:
            base["max-depth"] = str(max_depth)
        if page_size:
            base["page_size"] = str(page_size)
        if page_token:
            base["page_token"] = page_token
        if snaptoken:
            base["snaptoken"] = snaptoken
        if latest:
            base["latest"] = "true"
        return base

    def list_objects(
        self,
        subject: Subject | str,
        relation: str,
        namespace: str,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
        snaptoken: str = "",
        latest: bool = False,
    ) -> ListResult:
        """Every object the subject holds ``relation`` on in ``namespace``
        (reverse-index list serving; keto_tpu extension). A stale
        ``page_token`` — a write landed between pages — raises
        :class:`ErrStalePageToken`; restart from the first page."""
        if isinstance(subject, str):
            subject = SubjectID(id=subject)
        params = {"namespace": namespace, "relation": relation}
        params.update(_subject_params(subject))
        resp = self._request(
            "GET",
            f"{self.read_url}/relation-tuples/list-objects",
            params=self._list_params(
                params, max_depth, page_size, page_token, snaptoken, latest
            ),
        )
        doc = resp.json()
        return ListResult(
            items=doc.get("objects", []),
            next_page_token=doc.get("next_page_token", ""),
            snaptoken=doc.get("snaptoken", ""),
        )

    def list_subjects(
        self,
        namespace: str,
        object: str,
        relation: str,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
        snaptoken: str = "",
        latest: bool = False,
    ) -> ListResult:
        """Every subject id ``namespace:object#relation`` resolves to
        (keto_tpu extension; see :meth:`list_objects` for paging)."""
        resp = self._request(
            "GET",
            f"{self.read_url}/relation-tuples/list-subjects",
            params=self._list_params(
                {
                    "namespace": namespace,
                    "object": object,
                    "relation": relation,
                },
                max_depth, page_size, page_token, snaptoken, latest,
            ),
        )
        doc = resp.json()
        return ListResult(
            items=doc.get("subject_ids", []),
            next_page_token=doc.get("next_page_token", ""),
            snaptoken=doc.get("snaptoken", ""),
        )

    def get_relation_tuples(
        self,
        query: Optional[RelationQuery] = None,
        page_size: int = 0,
        page_token: str = "",
    ) -> TuplePage:
        params: dict = {}
        if query is not None:
            for k in ("namespace", "object", "relation"):
                v = getattr(query, k)
                if v is not None:
                    params[k] = v
            if query.subject is not None:
                params.update(_subject_params(query.subject))
        if page_size:
            params["page_size"] = str(page_size)
        if page_token:
            params["page_token"] = page_token
        resp = self._request(
            "GET", f"{self.read_url}/relation-tuples", params=params
        )
        doc = resp.json()
        return TuplePage(
            relation_tuples=[
                RelationTuple.from_dict(d) for d in doc["relation_tuples"]
            ],
            next_page_token=doc.get("next_page_token", ""),
        )

    def iter_relation_tuples(
        self, query: Optional[RelationQuery] = None, page_size: int = 0
    ) -> Iterable[RelationTuple]:
        """Auto-paginating iterator over matching tuples."""
        token = ""
        while True:
            page = self.get_relation_tuples(
                query, page_size=page_size, page_token=token
            )
            yield from page.relation_tuples
            token = page.next_page_token
            if not token:
                return

    # -- write plane -----------------------------------------------------------

    def create_relation_tuple(
        self, t: RelationTuple | str
    ) -> RelationTuple:
        t = RelationTuple.from_string(t) if isinstance(t, str) else t
        resp = self._request(
            "PUT",
            f"{self.write_url}/relation-tuples",
            ok=(201,),
            json=t.to_dict(),
        )
        return RelationTuple.from_dict(resp.json())

    def delete_relation_tuples(self, query: RelationQuery) -> None:
        params: dict = {}
        for k in ("namespace", "object", "relation"):
            v = getattr(query, k)
            if v is not None:
                params[k] = v
        if query.subject is not None:
            params.update(_subject_params(query.subject))
        self._request(
            "DELETE",
            f"{self.write_url}/relation-tuples",
            ok=(204,),
            params=params,
        )

    def patch_relation_tuples(
        self,
        insert: Sequence[RelationTuple] = (),
        delete: Sequence[RelationTuple] = (),
    ) -> None:
        """Atomic insert+delete transaction (PATCH deltas)."""
        deltas = [
            {"action": "insert", "relation_tuple": t.to_dict()}
            for t in insert
        ] + [
            {"action": "delete", "relation_tuple": t.to_dict()}
            for t in delete
        ]
        self._request(
            "PATCH",
            f"{self.write_url}/relation-tuples",
            ok=(204,),
            json=deltas,
        )

    # -- common ---------------------------------------------------------------

    def version(self) -> str:
        return self._request("GET", f"{self.read_url}/version").json()[
            "version"
        ]

    def alive(self) -> bool:
        return (
            self._http.get(f"{self.read_url}/health/alive").status_code
            == 200
        )

    def ready(self) -> bool:
        return (
            self._http.get(f"{self.read_url}/health/ready").status_code
            == 200
        )

    def metrics(self) -> str:
        return self._request("GET", f"{self.read_url}/metrics").text


class ReplicatedRestClient:
    """Reads fanned across a replicated read fleet, snaptoken-aware.

    One ``RestClient`` per read endpoint (followers and/or the leader's
    read plane) plus one write endpoint (the leader). Reads route
    through an ``EndpointRouter``: the primary endpoint prefers a
    replica already known to have replayed past the request's snaptoken
    (so the server-side freshness wait is a no-op), and any hedge fired
    by the ``Hedger`` lands on a *different* replica — the multi-replica
    generalization of RestClient.check_hedged's reissue-to-pool hedge.

    The router learns from routed traffic: a successful at-least-token
    read proves the endpoint reached that version; a shed/unavailable
    answer (including the follower's typed lag bounce) adds a decaying
    penalty. ``refresh_cluster_view`` folds a ``/cluster/status`` rollup
    in on top — red members demoted, leader remembered. Writes follow
    the leader: a 503 carrying a ``leader_hint`` (read-only follower or
    fenced ex-leader during an election) retargets the write endpoint
    and retries once, so a leadership change costs one round-trip, not
    an outage.
    """

    def __init__(
        self,
        read_urls: Sequence[str],
        write_url: Optional[str] = None,
        timeout: float = 30.0,
        verify=True,
        retry: Optional[RetryPolicy] = None,
        transport=None,
        hedger: Optional[Hedger] = None,
        router: Optional[EndpointRouter] = None,
    ):
        self.router = router or EndpointRouter(read_urls)
        self._clients = {
            ep: RestClient(
                ep,
                write_url=write_url,
                timeout=timeout,
                verify=verify,
                retry=retry,
                transport=transport,
            )
            for ep in self.router.endpoints
        }
        self._own_hedger = hedger is None
        self._hedger = hedger or Hedger()
        # any client reaches the single write endpoint; keep one handle
        self._writer = self._clients[self.router.endpoints[0]]

    def close(self) -> None:
        if self._own_hedger:
            self._hedger.close()
        for c in self._clients.values():
            c.close()

    def __enter__(self) -> "ReplicatedRestClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @staticmethod
    def _token_version(snaptoken: str) -> int:
        if not snaptoken:
            return 0
        from ..replication.token import parse_snaptoken

        try:
            return parse_snaptoken(snaptoken).version
        except ValueError:
            return 0  # let the server 400 it, not the router

    def _routed(self, endpoint: str, min_version: int, fn):
        """Run one attempt against ``endpoint``; feed the router."""
        try:
            result = fn(self._clients[endpoint])
        except (ErrResourceExhausted, ErrUnavailable):
            self.router.observe_error(endpoint)
            raise
        if min_version:
            # the read succeeded at-least-token: the endpoint has
            # necessarily replayed through the token's version
            self.router.observe_version(endpoint, min_version)
        return result

    def check(
        self,
        tuple_or_str: RelationTuple | str,
        max_depth: int = 0,
        snaptoken: str = "",
        latest: bool = False,
    ) -> CheckResult:
        """One hedged, routed check. Primary and hedge share a
        traceparent; the hedge carries ``x-keto-hedge: 1`` and goes to a
        different endpoint whenever the fleet has more than one."""
        mv = self._token_version(snaptoken)
        primary_ep, hedge_ep = self.router.pick(mv)
        tp = current_traceparent() or mint_traceparent()

        def attempt(endpoint: str, is_hedge: bool) -> CheckResult:
            return self._routed(
                endpoint,
                mv,
                lambda c: c.check(
                    tuple_or_str,
                    max_depth,
                    snaptoken,
                    latest,
                    traceparent=tp,
                    hedge=is_hedge,
                ),
            )

        call = self._hedger.call(
            lambda: attempt(primary_ep, False),
            hedge=(
                (lambda: attempt(hedge_ep, True))
                if hedge_ep is not None
                else None
            ),
        )
        return call.result

    def batch_check(
        self,
        tuples: Sequence[RelationTuple | str],
        max_depth: int = 0,
        snaptoken: str = "",
        latest: bool = False,
    ) -> list[bool]:
        """One routed batch check (not hedged: a batch duplicate doubles
        real work, unlike a single point read)."""
        mv = self._token_version(snaptoken)
        endpoint, _ = self.router.pick(mv)
        return self._routed(
            endpoint,
            mv,
            lambda c: c.batch_check(tuples, max_depth, snaptoken, latest),
        )

    def list_objects(
        self,
        subject: Subject | str,
        relation: str,
        namespace: str,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
        snaptoken: str = "",
        latest: bool = False,
    ) -> ListResult:
        """One routed list-objects read (not hedged: a list duplicate
        repeats a full row gather). The router prefers a replica already
        past the snaptoken, so the server-side freshness wait is a no-op;
        continuation pages carry their own version pin and 409 when any
        endpoint serves them a different store version."""
        mv = self._token_version(snaptoken)
        endpoint, _ = self.router.pick(mv)
        return self._routed(
            endpoint,
            mv,
            lambda c: c.list_objects(
                subject, relation, namespace, max_depth,
                page_size, page_token, snaptoken, latest,
            ),
        )

    def list_subjects(
        self,
        namespace: str,
        object: str,
        relation: str,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
        snaptoken: str = "",
        latest: bool = False,
    ) -> ListResult:
        mv = self._token_version(snaptoken)
        endpoint, _ = self.router.pick(mv)
        return self._routed(
            endpoint,
            mv,
            lambda c: c.list_subjects(
                namespace, object, relation, max_depth,
                page_size, page_token, snaptoken, latest,
            ),
        )

    def refresh_cluster_view(self) -> bool:
        """Fetch ``/cluster/status`` from the first answering endpoint
        and fold it into the router (health demotions, versions, leader
        coordinates). Best-effort: False when nobody answered."""
        for ep in self.router.endpoints:
            try:
                r = self._clients[ep]._http.get(f"{ep}/cluster/status")
                if r.status_code == 200:
                    self.router.observe_status(r.json())
                    return True
            except Exception:
                continue
        return False

    # -- write plane (leader-following) ----------------------------------------

    def _follow_leader(self, write_url: str) -> None:
        """Retarget every client's write endpoint at the new leader."""
        write_url = write_url.rstrip("/")
        for c in self._clients.values():
            c.write_url = write_url

    def _write(self, fn):
        """One write against the current leader; on a 503 that names a
        different leader (read-only follower, or an ex-leader fenced
        mid-election), follow the hint and retry exactly once."""
        try:
            return fn(self._writer)
        except ErrUnavailable as e:
            hint = getattr(e, "leader_hint", None)
            if isinstance(hint, dict):
                self.router.observe_leader(hint)
            else:
                hint = self.router.leader()
            target = str((hint or {}).get("write_url") or "").rstrip("/")
            if not target or target == self._writer.write_url:
                raise
            self._follow_leader(target)
            return fn(self._writer)

    def create_relation_tuple(self, t: RelationTuple | str) -> RelationTuple:
        return self._write(lambda c: c.create_relation_tuple(t))

    def patch_relation_tuples(
        self,
        insert: Sequence[RelationTuple] = (),
        delete: Sequence[RelationTuple] = (),
    ) -> None:
        self._write(
            lambda c: c.patch_relation_tuples(insert=insert, delete=delete)
        )

    def delete_relation_tuples(self, query: RelationQuery) -> None:
        self._write(lambda c: c.delete_relation_tuples(query))


class GrpcClient:
    """All five v1alpha1 services over read/write channels (same surface
    as the published Keto gRPC clients; stubs from keto_tpu.api.services)."""

    def __init__(
        self,
        read_target: str,
        write_target: Optional[str] = None,
        retry: Optional[RetryPolicy] = None,
        max_message_bytes: int = 64 << 20,
        retry_budget: Optional[RetryBudget] = None,
        criticality: Optional[str] = None,  # default shed class for checks
    ):
        import grpc

        from ..api.daemon import grpc_message_options
        from ..api.services import (
            CheckServiceStub,
            ExpandServiceStub,
            HealthStub,
            ListServiceStub,
            ReadServiceStub,
            VersionServiceStub,
            WriteServiceStub,
        )

        # default matches the server's serve.*.grpc-max-message-size
        # default so columnar batch payloads round-trip out of the box
        options = grpc_message_options(max_message_bytes)
        self._read_channel = grpc.insecure_channel(
            read_target, options=options
        )
        self._write_channel = (
            grpc.insecure_channel(write_target, options=options)
            if write_target
            else self._read_channel
        )
        self.check_service = CheckServiceStub(self._read_channel)
        self.expand_service = ExpandServiceStub(self._read_channel)
        self.read_service = ReadServiceStub(self._read_channel)
        self.list_service = ListServiceStub(self._read_channel)
        self.write_service = WriteServiceStub(self._write_channel)
        self.version_service = VersionServiceStub(self._read_channel)
        self.health = HealthStub(self._read_channel)
        self.retry = RetryPolicy() if retry is None else retry
        # shared retry-token bucket: caps this instance's retry
        # amplification at ~1.1x under sustained overload
        self.retry_budget = (
            RetryBudget() if retry_budget is None else retry_budget
        )
        self.criticality = criticality

    @staticmethod
    def _attach_retry_after(err: BaseException) -> None:
        """Copy the server's ``retry-after`` trailing-metadata hint onto
        the raised RpcError as ``retry_after_s`` so run_with_retry floors
        its backoff on it (the gRPC spelling of the Retry-After header)."""
        trailing = getattr(err, "trailing_metadata", None)
        if not callable(trailing):
            return
        try:
            for key, value in trailing() or ():
                if key == "retry-after":
                    err.retry_after_s = max(0.0, float(value))
                    return
        except Exception:
            pass

    def _call(self, rpc, request, timeout: Optional[float], metadata=None):
        """One retried unary RPC: UNAVAILABLE (server restarting, replica
        churn) and RESOURCE_EXHAUSTED (load shed before any work) back off
        and re-send; every attempt gets the REMAINING deadline budget so
        retrying never outlives the caller's timeout, and the shared
        retry budget caps total amplification. Writes are safe to
        retry here: transactions are idempotent per-delta and shed/
        unavailable mean the server did not execute. ``metadata`` (e.g. a
        traceparent entry) rides every attempt."""

        def attempt(remaining):
            try:
                if metadata:
                    return rpc(request, timeout=remaining, metadata=metadata)
                return rpc(request, timeout=remaining)
            except Exception as e:
                self._attach_retry_after(e)
                raise

        return run_with_retry(
            attempt,
            self.retry,
            grpc_retryable,
            timeout=timeout,
            budget=self.retry_budget,
        )

    @staticmethod
    def _trace_metadata(
        traceparent: Optional[str], hedge: bool
    ) -> tuple[str, tuple]:
        """(traceparent_used, invocation metadata) — the gRPC spelling of
        the REST _trace_headers helper."""
        tp = traceparent or current_traceparent() or mint_traceparent()
        metadata = [(TRACEPARENT_HEADER, tp)]
        if hedge:
            metadata.append((HEDGE_HEADER, "1"))
        return tp, tuple(metadata)

    def close(self) -> None:
        self._read_channel.close()
        if self._write_channel is not self._read_channel:
            self._write_channel.close()

    def __enter__(self) -> "GrpcClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- convenience wrappers --------------------------------------------------

    def check(
        self,
        t: RelationTuple | str,
        max_depth: int = 0,
        timeout: Optional[float] = None,
        traceparent: Optional[str] = None,
        hedge: bool = False,
        criticality: Optional[str] = None,
    ) -> CheckResult:
        from ..api import check_service_pb2
        from ..api.convert import subject_to_proto

        t = RelationTuple.from_string(t) if isinstance(t, str) else t
        tp, metadata = self._trace_metadata(traceparent, hedge)
        crit = criticality or self.criticality
        if crit:
            metadata = metadata + ((CRITICALITY_METADATA_KEY, crit),)
        resp = self._call(
            self.check_service.Check,
            check_service_pb2.CheckRequest(
                namespace=t.namespace,
                object=t.object,
                relation=t.relation,
                subject=subject_to_proto(t.subject),
                max_depth=max_depth,
            ),
            timeout,
            metadata=metadata,
        )
        return CheckResult(
            allowed=resp.allowed, snaptoken=resp.snaptoken, traceparent=tp
        )

    def check_hedged(
        self,
        t: RelationTuple | str,
        hedger: Hedger,
        max_depth: int = 0,
        timeout: Optional[float] = None,
    ):
        """Hedged single check over gRPC: primary and duplicate share one
        traceparent, duplicate tagged ``x-keto-hedge: 1`` on the
        invocation metadata (see RestClient.check_hedged)."""
        tp = current_traceparent() or mint_traceparent()

        def attempt(is_hedge: bool):
            return self.check(
                t, max_depth, timeout, traceparent=tp, hedge=is_hedge
            )

        return hedger.call(
            lambda: attempt(False), hedge=lambda: attempt(True)
        )

    def batch_check(
        self,
        tuples: Sequence[RelationTuple | str],
        max_depth: int = 0,
        snaptoken: str = "",
        latest: bool = False,
        timeout: Optional[float] = None,
        traceparent: Optional[str] = None,
        criticality: Optional[str] = None,
    ) -> list[bool]:
        """The BatchCheck RPC: many checks, one round trip, binary
        encoding (keto_tpu extension). `snaptoken`/`latest` apply
        at-least-as-fresh consistency to the whole batch."""
        from ..api import check_service_pb2
        from ..api.convert import subject_to_proto

        items = []
        for t in tuples:
            t = RelationTuple.from_string(t) if isinstance(t, str) else t
            items.append(
                check_service_pb2.CheckRequestTuple(
                    namespace=t.namespace,
                    object=t.object,
                    relation=t.relation,
                    subject=subject_to_proto(t.subject),
                )
            )
        _, metadata = self._trace_metadata(traceparent, hedge=False)
        crit = criticality or self.criticality
        if crit:
            metadata = metadata + ((CRITICALITY_METADATA_KEY, crit),)
        resp = self._call(
            self.check_service.BatchCheck,
            check_service_pb2.BatchCheckRequest(
                tuples=items,
                max_depth=max_depth,
                snaptoken=snaptoken,
                latest=latest,
            ),
            timeout,
            metadata=metadata,
        )
        return list(resp.allowed)

    def batch_check_encoded(
        self,
        cache: VocabCache,
        tuples: Sequence[RelationTuple | str],
        snaptoken: str = "",
        timeout: Optional[float] = None,
        traceparent: Optional[str] = None,
        max_resyncs: int = 2,
    ) -> list[bool]:
        """The id-native BatchCheckEncoded RPC: raw wirecodec frames over
        a no-serializer stub (packed int32 columns, no protobuf varint
        work). Epoch-mismatch bounces (FAILED_PRECONDITION with a
        ``vocab_epoch_mismatch`` detail) re-sync ``cache``, re-encode and
        re-send, at most ``max_resyncs`` times."""
        import json as _json

        import grpc

        from ..api import wirecodec

        mv = _snaptoken_version(snaptoken)
        tp, metadata = self._trace_metadata(traceparent, hedge=False)
        for attempt in range(max_resyncs + 1):
            start, target, ns = cache.encode(tuples)
            frame = wirecodec.encode_check_request(
                start,
                target,
                lineage=cache.lineage,
                epoch=cache.epoch,
                ns=ns,
                min_version=mv,
                traceparent=tp,
            )
            try:
                resp = self._call(
                    self.check_service.BatchCheckEncoded,
                    frame,
                    timeout,
                    metadata=metadata,
                )
            except grpc.RpcError as e:
                if (
                    e.code() == grpc.StatusCode.FAILED_PRECONDITION
                    and attempt < max_resyncs
                ):
                    details = {}
                    for k, v in e.trailing_metadata() or ():
                        if k == "keto-error-details":
                            try:
                                details = _json.loads(v)
                            except ValueError:
                                pass
                    if details.get("reason") == "vocab_epoch_mismatch":
                        cache.sync()
                        continue
                raise
            allowed, _tok = wirecodec.decode_check_response(resp)
            return [bool(v) for v in allowed]
        raise ErrUnavailable("encoded batch check exhausted resyncs")

    def transact(
        self,
        insert: Sequence[RelationTuple | str] = (),
        delete: Sequence[RelationTuple | str] = (),
        timeout: Optional[float] = None,
    ) -> str:
        """Atomic insert/delete transaction over WriteService; returns the
        commit snaptoken."""
        from ..api import write_service_pb2
        from ..api.convert import tuple_to_proto

        def deltas(tuples, action):
            for item in tuples:
                item = (
                    RelationTuple.from_string(item)
                    if isinstance(item, str)
                    else item
                )
                yield write_service_pb2.RelationTupleDelta(
                    action=action, relation_tuple=tuple_to_proto(item)
                )

        resp = self._call(
            self.write_service.TransactRelationTuples,
            write_service_pb2.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    *deltas(
                        insert, write_service_pb2.RelationTupleDelta.INSERT
                    ),
                    *deltas(
                        delete, write_service_pb2.RelationTupleDelta.DELETE
                    ),
                ]
            ),
            timeout,
        )
        return resp.snaptokens[0] if resp.snaptokens else ""

    def expand(
        self,
        subject_set: SubjectSet,
        max_depth: int = 0,
        timeout: Optional[float] = None,
    ) -> Optional[Tree]:
        from ..api import expand_service_pb2
        from ..api.convert import subject_to_proto, tree_from_proto

        resp = self._call(
            self.expand_service.Expand,
            expand_service_pb2.ExpandRequest(
                subject=subject_to_proto(subject_set),
                max_depth=max_depth,
            ),
            timeout,
        )
        if not resp.HasField("tree"):
            return None
        return tree_from_proto(resp.tree)

    def _list_call(
        self, rpc, body: dict, items_key: str, timeout: Optional[float]
    ) -> ListResult:
        # ListService speaks compact JSON bytes over identity serializers
        # (the checked-in protos predate the list surface; same transport
        # shape as BatchCheckEncoded)
        resp = self._call(
            rpc,
            json.dumps(
                {k: v for k, v in body.items() if v},
                separators=(",", ":"),
            ).encode(),
            timeout,
        )
        doc = json.loads(bytes(resp) or b"{}")
        return ListResult(
            items=doc.get(items_key, []),
            next_page_token=doc.get("next_page_token", ""),
            snaptoken=doc.get("snaptoken", ""),
        )

    def list_objects(
        self,
        subject: Subject | str,
        relation: str,
        namespace: str,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
        snaptoken: str = "",
        latest: bool = False,
        timeout: Optional[float] = None,
    ) -> ListResult:
        if isinstance(subject, str):
            subject = SubjectID(id=subject)
        body: dict = {
            "namespace": namespace,
            "relation": relation,
            "max_depth": max_depth,
            "page_size": page_size,
            "page_token": page_token,
            "snaptoken": snaptoken,
            "latest": latest,
        }
        if isinstance(subject, SubjectID):
            body["subject_id"] = subject.id
        else:
            body["subject_set"] = subject.to_dict()
        return self._list_call(
            self.list_service.ListObjects, body, "objects", timeout
        )

    def list_subjects(
        self,
        namespace: str,
        object: str,
        relation: str,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
        snaptoken: str = "",
        latest: bool = False,
        timeout: Optional[float] = None,
    ) -> ListResult:
        return self._list_call(
            self.list_service.ListSubjects,
            {
                "namespace": namespace,
                "object": object,
                "relation": relation,
                "max_depth": max_depth,
                "page_size": page_size,
                "page_token": page_token,
                "snaptoken": snaptoken,
                "latest": latest,
            },
            "subject_ids",
            timeout,
        )
