"""Temporal warm-start streaming/video stereo (docs/streaming.md).

Video makes RAFT-Stereo's iterative refinement a sequence problem: warm-
starting each frame from the previous frame's forward-warped disparity
(the RAFT warm-start policy, Teed & Deng ECCV 2020 — PAPERS.md) lets the
ConvGRU converge in a fraction of the cold-start iterations at equal
accuracy.  Layers, bottom-up:

* ``session``    — per-stream state (previous low-res disparity, sequence
                   number, update-magnitude EMA) in a bounded LRU + TTL
                   store; losing a session means a cold frame, never an
                   error.
* ``controller`` — adaptive iteration controller: picks each warm frame's
                   GRU iteration count from a small fixed ladder of
                   pre-compiled levels, steered by the EMA.
* ``runner``     — ``StreamRunner`` (frame stepper over the serve
                   ``BatchEngine``'s warm-start executables) plus the
                   offline ``run_sequence``/``compare_warm_cold`` harness
                   shared by ``cli/stream.py`` and the acceptance
                   tests.
* ``tier``       — durable session tier: a model-free shared store for
                   session snapshots (``cli.sessiontier`` service +
                   ``TierClient`` + the backends' write-behind
                   ``TierPublisher``), so any replica resumes any
                   stream warm even after its home backend is gone
                   (docs/streaming.md "Durable sessions").

Entry points: ``python -m raftstereo_tpu.cli.stream`` (offline sequence
runner), session-aware ``/predict`` (``session_id``/``seq_no``) on
``python -m raftstereo_tpu.cli.serve``.
"""

from .controller import AdaptiveIterController  # noqa: F401
from .runner import (  # noqa: F401
    StreamResult,
    StreamRunner,
    build_stream_engine,
    compare_warm_cold,
    run_sequence,
)
from .session import Session, SessionStore  # noqa: F401
from .tier import (  # noqa: F401
    SessionTier,
    TierClient,
    TierMetrics,
    TierPublisher,
    build_session_tier,
)
