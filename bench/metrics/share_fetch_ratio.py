"""``share_fetch_ratio``: bytes of the chunks a share's restore fetched
over the bytes the share holds. ``chunks`` is counted by the restore's
read itself (``core/service.ImageHandle.restore_share``: the distinct
chunk ciphertexts its ``restore_shards`` batch fetched through the
tiers, ``TieredReader.last_batch["names"]``), carried on its
``repro.restore.share`` span and returned in ``cold_start``'s stats;
the chunks the layout says the share lies in are ``chunks_covered``
beside it. The ratio rises with chunks that also hold bytes the replica
skips, the padding of each tensor's last chunk, or any chunk fetched
beyond the share; it falls where chunks of equal bytes are one name."""


def read(run):
    shares = [r["share"] for r in run.records if r.get("share")]
    if not shares:
        return None
    chunk = int(run.cell.traffic["chunk_bytes"])
    return (sum(s["chunks"] * chunk for s in shares)
            / sum(s["held_bytes"] for s in shares))
