"""Event store façade used by engine templates.

Parity with the reference's «data/.../data/store/{LEventStore,PEventStore}»
(SURVEY.md §2.2 [U]). In the reference, `PEventStore` returns RDDs for
training reads and `LEventStore` does driver-side lookups at serving time.
On TPU there is no RDD: training reads return plain Python lists / numpy
arrays that the host-side loader turns into device-sharded arrays, so P and L
collapse into one implementation with both spellings kept for familiarity.
"""

from __future__ import annotations

from datetime import datetime
from typing import Optional

from predictionio_tpu.data.datamap import PropertyMap, aggregate_properties
from predictionio_tpu.data.events import Event
from predictionio_tpu.storage.registry import Storage


class EventStore:
    def __init__(self, storage: Optional[Storage] = None):
        self._storage = storage

    def _resolve(self, app_name: str, channel_name: Optional[str]):
        storage = self._storage or Storage.get()
        app = storage.meta_apps().get_by_name(app_name)
        if app is None:
            raise ValueError(f"Invalid app name {app_name!r}")
        channel_id = None
        if channel_name is not None:
            channels = {c.name: c for c in storage.meta_channels().get_by_app_id(app.id)}
            if channel_name not in channels:
                raise ValueError(f"Invalid channel name {channel_name!r} for app {app_name!r}")
            channel_id = channels[channel_name].id
        return storage, app.id, channel_id

    def find(
        self,
        app_name: str,
        channel_name: Optional[str] = None,
        start_time: Optional[datetime] = None,
        until_time: Optional[datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[list[str]] = None,
        target_entity_type: Optional[str] = None,
        target_entity_id: Optional[str] = None,
        limit: Optional[int] = None,
        reversed: bool = False,
    ) -> list[Event]:
        storage, app_id, channel_id = self._resolve(app_name, channel_name)
        return list(
            storage.l_events().find(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
                limit=limit,
                reversed=reversed,
            )
        )

    def find_by_entity(
        self,
        app_name: str,
        entity_type: str,
        entity_id: str,
        channel_name: Optional[str] = None,
        event_names: Optional[list[str]] = None,
        target_entity_type: Optional[str] = None,
        target_entity_id: Optional[str] = None,
        start_time: Optional[datetime] = None,
        until_time: Optional[datetime] = None,
        limit: Optional[int] = None,
        latest: bool = True,
    ) -> list[Event]:
        """Serving-time lookup (`LEventStore.findByEntity` [U]) — the E-Comm
        template calls this on the query hot path (SURVEY.md §3.2)."""
        return self.find(
            app_name=app_name,
            channel_name=channel_name,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            entity_id=entity_id,
            event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id,
            limit=limit,
            reversed=latest,
        )

    def find_columnar(
        self,
        app_name: str,
        channel_name: Optional[str] = None,
        start_time: Optional[datetime] = None,
        until_time: Optional[datetime] = None,
        entity_type: Optional[str] = None,
        target_entity_type: Optional[str] = None,
        event_names: Optional[list[str]] = None,
        value_key: Optional[str] = None,
        ordered: bool = True,
    ):
        """Bulk columnar training read — integer-coded numpy columns, no
        per-event Python objects (the RDD-scan role of «HBPEvents» [U];
        see `storage/base.py::LEvents.find_columnar`). This is what
        template `read_training`s should call at 2M+ events.
        `ordered=False` skips the output time-sort for order-invariant
        consumers (ALS).
        """
        storage, app_id, channel_id = self._resolve(app_name, channel_name)
        return storage.l_events().find_columnar(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            target_entity_type=target_entity_type,
            event_names=event_names,
            value_key=value_key,
            ordered=ordered,
        )

    def aggregate_properties(
        self,
        app_name: str,
        entity_type: str,
        channel_name: Optional[str] = None,
        start_time: Optional[datetime] = None,
        until_time: Optional[datetime] = None,
        required: Optional[list[str]] = None,
    ) -> dict[str, PropertyMap]:
        """`$set/$unset/$delete`-folded entity state (`aggregateProperties` [U]).

        Reads through the pushed-down columnar fold when the backend has
        one (C++ / SQL tiers in `storage/sqlite.py` — no per-event Python
        object; 11.3× the per-event path at 2M property events, round 4),
        falling back to the per-event
        `data/datamap.py::aggregate_properties` fold, which is the
        semantics oracle the pushdown tiers are tested against.
        `PIO_AGG_PUSHDOWN=0` forces the per-event fold (ops escape
        hatch + the A/B lever the measured receipts use)."""
        import os

        storage, app_id, channel_id = self._resolve(app_name, channel_name)
        agg = None
        if os.environ.get("PIO_AGG_PUSHDOWN", "1") != "0":
            agg = storage.l_events().aggregate_properties_columnar(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                required=list(required) if required else None,
            )
        if agg is not None:
            return {
                eid: PropertyMap(fields, first_updated=first, last_updated=last)
                for eid, (fields, first, last) in agg.items()
            }
        events = self.find(
            app_name=app_name,
            channel_name=channel_name,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=["$set", "$unset", "$delete"],
        )
        props = aggregate_properties(events)
        if required:
            props = {
                eid: p for eid, p in props.items() if all(k in p for k in required)
            }
        return props


# The two reference spellings; `PEventStore` for training reads,
# `LEventStore` for serving-time lookups. Same implementation on TPU.
PEventStore = EventStore
LEventStore = EventStore
