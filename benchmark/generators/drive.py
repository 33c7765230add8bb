"""Drive-style documents in nested folders, written as Ory Keto's
cat-videos example writes files and directories: materialized subject
sets, no userset rewrites.

Namespace ``namespace`` holds documents ``d{}`` and folders ``f{}``, each
with an ``owner`` and a ``view`` set; namespace ``group_namespace`` holds
groups ``g{}#member``. The tuples:

- ``d#owner@user`` and ``f#owner@user``: one owner each, uniform over the
  users;
- ``d#view@(d#owner)`` and ``f#view@(f#owner)``: owners can view;
- ``d#view@(f#view)``: a document inherits ``view`` from its folder,
  uniform over all folders;
- ``f#view@(p#view)``: a folder of level l >= 1 inherits from a parent
  of level l - 1 (``folder_levels`` levels of equal size, level 0 the
  roots);
- ``g#member@user``: users in groups, uniform;
- ``g#member@(h#member)``: group nesting, a group of level l + 1 inside
  one of level l (``group_levels`` levels of equal size);
- shares, topped up until the store holds exactly ``tuples`` distinct
  tuples: ``share_folder`` of them on a folder's ``view`` and the rest on
  a document's, ``share_to_group`` of them to a group's ``member`` set and
  the rest to a user.

The longest path is a document in a folder of the deepest level, up the
folder chain to a root, a share to a group and the groups nested below
it: 1 + (folder_levels - 1) + 1 + (group_levels - 1) + 1 tuples.

Every document's owner set, every folder set and every shared or nested
group is a subject set with an incoming edge: the interior grows with
documents and folders. Drawn with numpy from the seed.
"""

from __future__ import annotations

import numpy as np

from ..graph import Graph, Kind, fill, pick


def _levelled(kind: Kind, levels: int) -> int:
    return kind.count // levels


def build(sizes: dict, rng) -> Graph:
    n = int(sizes["tuples"])
    ns, gns = sizes["namespace"], sizes["group_namespace"]
    n_docs, n_folders = int(sizes["docs"]), int(sizes["folders"])
    users = Kind("user", 0, int(sizes["users"]), ("u{}",))
    doc_view = Kind("doc_view", users.stop, n_docs, (ns, "d{}", "view"))
    doc_owner = Kind("doc_owner", doc_view.stop, n_docs,
                     (ns, "d{}", "owner"))
    folder_view = Kind("folder_view", doc_owner.stop, n_folders,
                       (ns, "f{}", "view"))
    folder_owner = Kind("folder_owner", folder_view.stop, n_folders,
                        (ns, "f{}", "owner"))
    groups = Kind("group", folder_owner.stop, int(sizes["groups"]),
                  (gns, "g{}", "member"))
    f_levels = int(sizes["folder_levels"])
    g_levels = int(sizes["group_levels"])
    f_per = _levelled(folder_view, f_levels)
    g_per = _levelled(groups, g_levels)
    docs = np.arange(n_docs, dtype=np.int64)
    folders = np.arange(n_folders, dtype=np.int64)

    # folders of levels 1 .. f_levels - 1, each under a parent one level up
    child = folders[f_per: f_levels * f_per]
    parent = (child // f_per - 1) * f_per + rng.integers(f_per,
                                                         size=len(child))

    def nesting(k):
        # group (level l) gets member group (level l + 1)
        lv = groups.start + rng.integers(g_levels - 1, size=k) * g_per
        outer = lv + rng.integers(g_per, size=k)
        inner = lv + g_per + rng.integers(g_per, size=k)
        return outer, inner

    def share(rng, k):
        on_folder = rng.random(k) < sizes["share_folder"]
        obj = np.where(on_folder, pick(folder_view, rng, k),
                       pick(doc_view, rng, k))
        to_group = rng.random(k) < sizes["share_to_group"]
        sub = np.where(to_group, pick(groups, rng, k), pick(users, rng, k))
        return obj, sub

    n_member = int(sizes["group_members"])
    stages = [
        (doc_owner.start + docs, pick(users, rng, n_docs)),
        (doc_view.start + docs, doc_owner.start + docs),
        (doc_view.start + docs, pick(folder_view, rng, n_docs)),
        (folder_owner.start + folders, pick(users, rng, n_folders)),
        (folder_view.start + folders, folder_owner.start + folders),
        (folder_view.start + child, folder_view.start + parent),
        (pick(groups, rng, n_member), pick(users, rng, n_member)),
        nesting(int(sizes["group_nesting"])),
    ]
    src, dst = fill(stages, share, n, groups.stop, rng)
    return Graph(
        kinds=[users, doc_view, doc_owner, folder_view, folder_owner,
               groups],
        src=src,
        dst=dst,
        objects="doc_view",
        subjects="user",
        grants=("doc_view",),
    )


def expected_interior(sizes: dict) -> int:
    """Interior subject sets the sizes give, without building the graph:
    every document owner set, every folder view and owner set, and every
    group (a group with no share and no parent group is rare at these
    sizes and counted anyway)."""
    return (int(sizes["docs"]) + 2 * int(sizes["folders"])
            + int(sizes["groups"]))


def longest_path(sizes: dict) -> int:
    """Tuples on the longest inheritance path (module docstring)."""
    return 1 + (int(sizes["folder_levels"]) - 1) + 1 + (
        int(sizes["group_levels"]) - 1) + 1
