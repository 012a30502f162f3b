"""Async HTTP with retry (reference: areal/utils/http.py arequest_with_retry).

Retry semantics (ISSUE 11 satellite): failures fall into three classes
and only two of them are always safe to retry.

- *never sent* (connect refused / DNS / connect-phase timeout): the
  handler provably did not run — always retryable.
- *retryable status* (408/425/429/5xx): the server answered and asked
  for / implies a retry, but for 5xx the handler may have partially run,
  so a non-idempotent request must not be replayed blindly.
- *ambiguous* (read timeout, mid-response disconnect): the request may
  have committed server-side; replaying a non-idempotent request here
  double-applies it.

Callers declare ``idempotent=`` honestly: GETs and version polls are,
`/generate` (slot allocation + staleness accounting per call) is not —
the remote client owns its own failover/resubmit loop for those.
Other 4xx raise immediately with ``.status`` set (a 409 staleness
rejection must surface, not burn the retry budget).
"""

import asyncio
import random
from typing import Any, Dict, Optional

import aiohttp

from areal_tpu.utils import logging

logger = logging.getLogger("http")

# Statuses worth retrying besides 5xx: request-timeout, too-early,
# rate-limited.  Everything else in 4xx is the caller's bug or an
# application-level rejection and must surface immediately.
RETRYABLE_STATUSES = frozenset({408, 425, 429})


def is_retryable_status(status: int) -> bool:
    return status in RETRYABLE_STATUSES or status >= 500


def _backoff(retry_delay: float, attempt: int) -> float:
    # Full jitter: uniform over [0, cap) so a killed backend's clients
    # don't re-converge on the survivor in synchronized waves.
    return random.uniform(0, retry_delay * (2**attempt))


def get_default_connector() -> aiohttp.TCPConnector:
    # A fresh connector per session: sessions are created per-request-context
    # on the runner's event loop, and connectors cannot be shared across loops.
    return aiohttp.TCPConnector(limit=0, ttl_dns_cache=300)


class HttpRequestError(RuntimeError):
    def __init__(self, message: str, status: Optional[int] = None):
        super().__init__(message)
        self.status = status


def _never_sent(exc: BaseException) -> bool:
    """True when the request provably never reached a handler."""
    return isinstance(
        exc,
        (
            aiohttp.ClientConnectorError,
            aiohttp.ClientProxyConnectionError,
            ConnectionRefusedError,
        ),
    )


async def arequest_with_retry(
    addr: str,
    endpoint: str,
    payload: Optional[Dict[str, Any]] = None,
    method: str = "POST",
    max_retries: int = 3,
    timeout: float = 3600,
    retry_delay: float = 0.5,
    session: Optional[aiohttp.ClientSession] = None,
    data: Optional[bytes] = None,
    headers: Optional[Dict[str, str]] = None,
    idempotent: bool = True,
) -> Dict[str, Any]:
    """JSON request (default) or raw-bytes upload (`data` + `headers`)
    with retry/backoff.  `timeout` applies per request even on a shared
    session (aiohttp per-request override).  With ``idempotent=False``,
    only never-sent connection failures are retried; ambiguous failures
    and 5xx raise so the caller can decide (e.g. fail over)."""
    url = f"http://{addr}{endpoint}"
    last_exc: Optional[BaseException] = None
    owns_session = session is None
    if owns_session:
        session = aiohttp.ClientSession(connector=get_default_connector())
    req_timeout = aiohttp.ClientTimeout(
        total=timeout, sock_connect=min(30, timeout)
    )
    try:
        for attempt in range(max_retries):
            try:
                kwargs: Dict[str, Any] = {"timeout": req_timeout}
                if data is not None:
                    kwargs["data"] = data
                    kwargs["headers"] = {
                        "Content-Type": "application/octet-stream",
                        **(headers or {}),
                    }
                elif method != "GET":
                    kwargs["json"] = payload
                async with session.request(method, url, **kwargs) as resp:
                    if resp.status == 200:
                        ctype = resp.headers.get("Content-Type", "")
                        if "application/json" in ctype:
                            return await resp.json()
                        return {"text": await resp.text()}
                    body = await resp.text()
                    last_exc = HttpRequestError(
                        f"{method} {url} -> HTTP {resp.status}: {body[:200]}",
                        status=resp.status,
                    )
                    if not is_retryable_status(resp.status):
                        raise last_exc
                    if not idempotent:
                        # the handler ran (5xx may have side effects):
                        # replaying a non-idempotent request is on the caller
                        raise last_exc
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
                last_exc = e
                if not idempotent and not _never_sent(e):
                    # ambiguous: sent but outcome unknown — don't replay
                    raise HttpRequestError(
                        f"{method} {url} failed ambiguously "
                        f"(non-idempotent, not retried): {e!r}"
                    ) from e
            if attempt < max_retries - 1:
                await asyncio.sleep(_backoff(retry_delay, attempt))
        raise HttpRequestError(
            f"request to {url} failed after {max_retries} attempts",
            status=getattr(last_exc, "status", None),
        ) from last_exc
    finally:
        if owns_session:
            await session.close()


async def apost_bytes_with_retry(
    addr: str,
    endpoint: str,
    data: bytes,
    headers: Optional[Dict[str, str]] = None,
    max_retries: int = 3,
    timeout: float = 3600,
    retry_delay: float = 0.5,
    session: Optional[aiohttp.ClientSession] = None,
    idempotent: bool = True,
) -> Dict[str, Any]:
    """POST a raw `application/octet-stream` body (weight-chunk fast path:
    no base64 inflation, no json parse per chunk)."""
    return await arequest_with_retry(
        addr=addr,
        endpoint=endpoint,
        method="POST",
        max_retries=max_retries,
        timeout=timeout,
        retry_delay=retry_delay,
        session=session,
        data=data,
        headers=headers,
        idempotent=idempotent,
    )

