"""Model step, for a latent-attention decoder whose decode step walks
its slots' page tables: how many times a live latent page is read a
layer -- the pages the steps' walks read
(``serve_decode_view_pages_read_total``: the live pages of every active
slot, a page several slots share once a SLOT) over the distinct pages
among them (``serve_latent_pages_live_total``, a shared page once),
both over the window. 1 where no two slots share a page; with sixteen
slots over seven documents about 2.2: what one read of a shared page
(ROADMAP B-M4c) would still save. A program that counts no distinct
latent pages, or whose decode steps read every slot's whole capacity
(a gathered rectangle: it reads a view, not pages), reports nothing."""


def read(obs):
    stats = (obs.get("serve") or {}).get("stats") or {}
    live = stats.get("serve_latent_pages_live_total")
    walked = stats.get("serve_decode_view_pages_read_total")
    if not live or walked is None:
        return None
    if walked >= stats.get("serve_decode_view_pages_total", 0):
        return None
    return walked / live
