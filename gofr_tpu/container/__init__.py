"""Container: the dependency-injection hub handed to every handler.

Parity: reference pkg/gofr/container/ — Container struct (container.go:28-41),
Create wiring from config (container.go:73-154), framework metrics
registration (container.go:166-198), health aggregation (health.go:8-28),
datasource interface seams (datasources.go:13-33).

TPU-first addition: the container owns the TPURuntime (model registry +
device mesh + dynamic batchers) exactly as it owns Redis/SQL in the
reference — `ctx.tpu()` is a datasource.
"""

from __future__ import annotations

import time
from typing import Any

from .. import logging as gl
from ..config import Config
from ..logging.remote import RemoteLevelLogger
from ..metrics import (
    DATASOURCE_BUCKETS,
    HTTP_BUCKETS,
    TPU_BUCKETS,
    Manager,
    new_metrics_manager,
)
from ..version import FRAMEWORK


class Container:
    """Holds logger, config, metrics, datasources, outbound services, TPU."""

    def __init__(self, config: Config | None = None, logger: gl.Logger | None = None):
        self.config = config
        self.logger: gl.Logger = logger or gl.new_logger()
        self.app_name = "gofr-tpu-app"
        self.app_version = "dev"
        self.services: dict[str, Any] = {}  # outbound HTTP services
        self.metrics_manager: Manager | None = None
        self.redis = None
        self.sql = None
        self.pubsub = None
        self.mongo = None
        self.tpu_runtime = None
        # scale-out proxy core (gofr_tpu.router.new_router_app attaches)
        self.front_router = None
        self.start_time = time.time()

    # -- construction (container.go:73-154) --
    @classmethod
    def create(cls, config: Config) -> "Container":
        c = cls(config=config)
        c.app_name = config.get_or_default("APP_NAME", "gofr-tpu-app")
        c.app_version = config.get_or_default("APP_VERSION", "dev")

        # TPU_PLATFORM=cpu|tpu pins the jax backend. Applied here — before
        # any user code can touch jax — because backend choice is global and
        # first-touch-wins (the runtime re-checks, but by then user model
        # init may already have initialized the wrong platform). A platform
        # that did not take raises.
        platform = config.get("TPU_PLATFORM")
        if platform:
            from ..utils import pin_jax_platform

            pin_jax_platform(platform)

        c.logger = RemoteLevelLogger(
            gl.level_from_string(config.get("LOG_LEVEL")),
            config.get("REMOTE_LOG_URL") or None,
            config.get_float("REMOTE_LOG_FETCH_INTERVAL", 15.0),
        )
        c.logger.debug("Container is being created")

        c.metrics_manager = new_metrics_manager(c.logger)
        c.register_framework_metrics()
        c.metrics_manager.set_gauge(
            "app_info", 1.0, app_name=c.app_name, app_version=c.app_version, framework_version=FRAMEWORK
        )

        # Datasources are wired only when configured, as in the reference.
        if config.get("REDIS_HOST"):
            from ..datasource.redis import new_client as new_redis

            c.redis = new_redis(config, c.logger, c.metrics_manager)
        if config.get("DB_DIALECT") or config.get("DB_HOST"):
            from ..datasource.sql import new_sql

            c.sql = new_sql(config, c.logger, c.metrics_manager)
        backend = (config.get("PUBSUB_BACKEND") or "").upper()
        if backend:
            from ..datasource.pubsub import new_pubsub

            c.pubsub = new_pubsub(backend, config, c.logger, c.metrics_manager)

        # TPU runtime is lazy: devices are touched on first use or when the
        # app registers a model, so pure-web apps never initialize jax.
        return c

    def register_framework_metrics(self) -> None:
        """Parity: container.go:166-198 (renamed go->python runtime gauges)."""
        m = self.metrics_manager
        assert m is not None
        m.new_gauge("app_info", "static app info")
        m.new_gauge("app_python_threads", "live thread count")
        m.new_gauge("app_sys_memory_rss", "resident set size bytes")
        m.new_gauge("app_python_gc_gen0", "gen0 allocations since last gc")
        m.new_gauge("app_python_num_gc", "completed gc collections")
        m.new_histogram("app_http_response", "http server response time s", HTTP_BUCKETS)
        m.new_histogram("app_http_service_response", "outbound http call time s", HTTP_BUCKETS)
        m.new_histogram("app_redis_stats", "redis op time s", DATASOURCE_BUCKETS)
        m.new_histogram("app_sql_stats", "sql op time s", DATASOURCE_BUCKETS)
        m.new_histogram("app_mongo_stats", "mongo op time s", DATASOURCE_BUCKETS)
        m.new_gauge("app_sql_open_connections", "open sql connections")
        m.new_gauge("app_sql_inuse_connections", "in-use sql connections")
        # TPU datasource metrics (the build's app_tpu_stats analogue of app_sql_stats)
        m.new_histogram("app_tpu_stats", "tpu execute time s", TPU_BUCKETS)
        m.new_histogram("app_tpu_batch_size", "dynamic batch sizes", (1, 2, 4, 8, 16, 32, 64, 128, 256))
        m.new_histogram("app_tpu_queue_wait", "batch queue wait s", TPU_BUCKETS)
        # Pub/sub counters (container.go:194-197)
        m.new_counter("app_pubsub_publish_total_count", "messages published")
        m.new_counter("app_pubsub_publish_success_count", "messages published ok")
        m.new_counter("app_pubsub_subscribe_total_count", "subscribe receives")
        m.new_counter("app_pubsub_subscribe_success_count", "messages handled ok")

    # -- TPU runtime accessor --
    def tpu(self):
        if self.tpu_runtime is None:
            from ..datasource.tpu import TPURuntime

            self.tpu_runtime = TPURuntime(
                self.config, self.logger, self.metrics_manager,
                # the App sets container.tracer after create(); engines
                # registered before that (rare) simply serve untraced
                tracer=getattr(self, "tracer", None),
            )
        return self.tpu_runtime

    # -- health aggregation (health.go:8-28) --
    def health(self) -> dict[str, Any]:
        out: dict[str, Any] = {}
        if self.sql is not None:
            out["sql"] = self.sql.health_check()
        if self.redis is not None:
            out["redis"] = self.redis.health_check()
        if self.pubsub is not None:
            out["pubsub"] = self.pubsub.health()
        if self.mongo is not None:
            out["mongo"] = self.mongo.health_check()
        if self.tpu_runtime is not None:
            out["tpu"] = self.tpu_runtime.health_check()
        for name, svc in self.services.items():
            try:
                out[name] = svc.health_check_sync()
            except Exception as e:  # noqa: BLE001
                out[name] = {"status": "DOWN", "details": {"error": str(e)}}
        out["app"] = {
            "status": "UP",
            "details": {
                "name": self.app_name,
                "version": self.app_version,
                "framework": FRAMEWORK,
                "uptime_s": round(time.time() - self.start_time, 3),
            },
        }
        return out

    def get_http_service(self, name: str):
        return self.services.get(name)

    def get_publisher(self):
        return self.pubsub

    def get_subscriber(self):
        return self.pubsub

    # -- metrics facade for user code (examples/using-custom-metrics) --
    @property
    def metrics(self) -> Manager:
        assert self.metrics_manager is not None, "metrics not initialized"
        return self.metrics_manager

    def add_mongo(self, provider) -> None:
        """Wire a user-constructed Mongo provider (externalDB.go:5-12):
        inject logger/metrics, connect, expose as ctx.mongo."""
        from ..datasource.mongo import InstrumentedMongo

        db = InstrumentedMongo(provider, self.logger, self.metrics_manager)
        provider.connect()
        self.mongo = db

    def close(self) -> None:
        # front_router: the scale-out proxy core (poll thread, breaker
        # probes, autoscaler-managed engine processes) — attached by
        # gofr_tpu.router.new_router_app
        for attr in ("redis", "sql", "pubsub", "mongo", "tpu_runtime",
                     "front_router"):
            ds = getattr(self, attr)
            if ds is not None and hasattr(ds, "close"):
                try:
                    ds.close()
                except Exception:  # noqa: BLE001
                    pass
        if isinstance(self.logger, RemoteLevelLogger):
            self.logger.close()


def new_container(config: Config) -> Container:
    return Container.create(config)
