"""Count what JAX's persistent compilation cache served and compiled.

Reads the records of JAX's compiler logger: every executable JAX compiles
or loads passes through it once, so a record inside the measured window
means something compiled (or was loaded) there.
"""

from __future__ import annotations

import logging
import re


class CacheLog(logging.Handler):
    """Names of the executables the persistent cache served (``hit``),
    compiled (``miss``) and did not keep (``not_kept``); other records of
    WARNING and above still reach stderr."""

    PATTERNS = (("hit", re.compile(r"cache hit for '([^']+)'")),
                ("miss", re.compile(r"CACHE MISS for '([^']+)'")),
                ("not_kept", re.compile(r"entry for '([^']+)' because it "
                                        r"took <")))

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = {kind: [] for kind, _ in self.PATTERNS}
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.propagate = False
        logger.addHandler(self)

    def emit(self, record):
        msg = record.getMessage()
        for kind, pat in self.PATTERNS:
            m = pat.search(msg)
            if m:
                self.names[kind].append(m.group(1))
                return
        if record.levelno >= logging.WARNING:
            logging.lastResort.handle(record)

    def count(self, kind: str) -> int:
        return len(self.names[kind])

    def total(self) -> int:
        """Executables compiled or loaded so far (hits and misses)."""
        return self.count("hit") + self.count("miss")
