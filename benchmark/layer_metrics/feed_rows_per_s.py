"""Feed plane: rows ``ctx.get_data_feed`` delivered (``feed_items``) over the
window.  Cells without a DataFeed (FILES mode) have nothing to read."""


def read(report):
    rows = report["window"]["delta"].get("feed", {}).get("feed_items")
    if rows is None:
        return None
    return rows / report["window"]["seconds"]
