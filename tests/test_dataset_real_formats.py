"""Every dataset module must parse the REFERENCE's real on-disk format. Each test builds a tiny format-faithful fixture
(the same container type, member layout and record syntax as the upstream
release), points DATA_HOME at it, and checks the reader yields the real
records — then that removing the fixture falls back to synthetic."""

import gzip
import io
import os
import pickle
import tarfile
import zipfile

import numpy as np
import pytest

import paddle_tpu.dataset as ds
from paddle_tpu.dataset import common


@pytest.fixture()
def data_home(tmp_path, monkeypatch):
    monkeypatch.setattr(common, "DATA_HOME", str(tmp_path))
    # modules with parse-once metadata caches must not leak between tests
    monkeypatch.setattr(ds.movielens, "_MOVIE_INFO", None)
    monkeypatch.setattr(ds.movielens, "_USER_INFO", None)
    monkeypatch.setattr(ds.sentiment, "_DATA_CACHE", None)
    monkeypatch.setattr(ds.imdb, "_DICT_CACHE", None)
    return tmp_path


def _tar_bytes(tar, name, payload):
    info = tarfile.TarInfo(name)
    info.size = len(payload)
    tar.addfile(info, io.BytesIO(payload))


# --- cifar -------------------------------------------------------------------

def test_cifar10_parses_pickled_tarball(data_home):
    d = data_home / "cifar"
    d.mkdir()
    rng = np.random.RandomState(0)
    with tarfile.open(d / "cifar-10-python.tar.gz", "w:gz") as tar:
        for name, labels in (("cifar-10-batches-py/data_batch_1", [3, 7]),
                             ("cifar-10-batches-py/test_batch", [1])):
            batch = {"data": rng.randint(0, 256, (len(labels), 3072))
                     .astype(np.uint8),
                     "labels": labels}
            _tar_bytes(tar, name, pickle.dumps(batch, protocol=2))
    got = list(ds.cifar.train10()())
    assert len(got) == 2
    img, label = got[0]
    assert img.shape == (3072,) and img.dtype == np.float32
    assert 0.0 <= img.min() and img.max() <= 1.0 and label == 3
    assert [lab for _, lab in ds.cifar.test10()()] == [1]


def test_cifar100_uses_fine_labels(data_home):
    d = data_home / "cifar"
    d.mkdir()
    rng = np.random.RandomState(1)
    with tarfile.open(d / "cifar-100-python.tar.gz", "w:gz") as tar:
        batch = {"data": rng.randint(0, 256, (2, 3072)).astype(np.uint8),
                 "fine_labels": [42, 99]}
        _tar_bytes(tar, "cifar-100-python/train",
                   pickle.dumps(batch, protocol=2))
    assert [lab for _, lab in ds.cifar.train100()()] == [42, 99]


# --- imdb --------------------------------------------------------------------

def test_imdb_parses_aclimdb_tarball(data_home):
    d = data_home / "imdb"
    d.mkdir()
    docs = {
        "aclImdb/train/pos/0_9.txt": b"A great, GREAT movie! great fun",
        "aclImdb/train/neg/0_1.txt": b"terrible. just terrible terrible",
        "aclImdb/test/pos/0_8.txt": b"great great great great",
        "aclImdb/test/neg/0_2.txt": b"terrible terrible terrible plot",
    }
    with tarfile.open(d / "aclImdb_v1.tar.gz", "w:gz") as tar:
        for name, text in docs.items():
            _tar_bytes(tar, name, text)
    w = ds.imdb.build_dict(
        __import__("re").compile(r"aclImdb/train/.*\.txt$"), cutoff=1)
    # punctuation stripped + lowercased: 'great' (4x) ranks before
    # 'terrible' (3x in train)
    assert w["great"] == 0 and w["terrible"] == 1
    assert "<unk>" in w
    got = list(ds.imdb.train(w)())
    assert len(got) == 2
    (pos_ids, pos_lab), (neg_ids, neg_lab) = got
    assert pos_lab == 0 and neg_lab == 1          # reference's assignment
    assert pos_ids.count(w["great"]) == 3         # 'great,' and 'GREAT!'
    assert all(isinstance(i, int) for i in pos_ids)


# --- imikolov ----------------------------------------------------------------

def test_imikolov_ngram_and_seq(data_home):
    d = data_home / "imikolov"
    d.mkdir()
    train_txt = b" the cat sat \n the cat ran \n"
    valid_txt = b" the cat sat \n"
    with tarfile.open(d / "simple-examples.tgz", "w:gz") as tar:
        _tar_bytes(tar, "./simple-examples/data/ptb.train.txt", train_txt)
        _tar_bytes(tar, "./simple-examples/data/ptb.valid.txt", valid_txt)
    w = ds.imikolov.build_dict(min_word_freq=0)
    assert w["<unk>"] == len(w) - 1
    assert set(w) == {"the", "cat", "sat", "ran", "<s>", "<e>", "<unk>"}
    grams = list(ds.imikolov.train(w, n=2)())
    # line 1: <s> the cat sat <e> -> 4 bigrams; line 2 same count
    assert len(grams) == 8
    assert grams[0] == (w["<s>"], w["the"])
    seqs = list(ds.imikolov.train(w, n=10,
                                  data_type=ds.imikolov.DataType.SEQ)())
    assert seqs[0][0] == [w["<s>"], w["the"], w["cat"], w["sat"]]
    assert seqs[0][1] == [w["the"], w["cat"], w["sat"], w["<e>"]]


# --- movielens ---------------------------------------------------------------

def test_movielens_parses_ml1m_zip(data_home):
    d = data_home / "movielens"
    d.mkdir()
    with zipfile.ZipFile(d / "ml-1m.zip", "w") as z:
        z.writestr("ml-1m/movies.dat",
                   "1::Toy Story (1995)::Animation|Comedy\n"
                   "2::Heat (1995)::Action\n")
        z.writestr("ml-1m/users.dat",
                   "1::F::1::10::48067\n2::M::56::16::70072\n")
        z.writestr("ml-1m/ratings.dat",
                   "1::1::5::978300760\n2::2::1::978298413\n")
    samples = list(ds.movielens.train()())
    # test_ratio split may route either record to test; whichever remain
    # must carry parsed metadata
    assert samples
    for s in samples:
        uid, gender, age, job, mid, cats, titles, score = s
        if uid == [1]:
            assert gender == [1]                  # F -> 1
            assert age == [0] and job == [10] and mid == [1]
            assert len(cats) == 2 and len(titles) == 2   # 'Toy Story'
            assert score == [5.0 * 2 - 5.0]
        else:
            assert uid == [2] and gender == [0]   # M -> 0
            assert age == [6]                     # 56 -> index 6
            assert score == [1.0 * 2 - 5.0]
    assert ds.movielens.max_user_id() == 2
    assert ds.movielens.max_movie_id() == 2
    cats = ds.movielens.movie_categories()
    assert set(cats) == {"Animation", "Comedy", "Action"}
    title_dict = ds.movielens.get_movie_title_dict()
    assert "toy" in title_dict and "heat" in title_dict


# --- conll05 -----------------------------------------------------------------

def test_conll05_parses_props_brackets(data_home):
    d = data_home / "conll05st"
    d.mkdir()
    # two-predicate sentence in the real column format: col0 = verb lemma
    # or '-', one tag-stream column per predicate
    words = "The\ncat\nchased\na\ndog\n\n"
    props = ("-   (A0*  *\n"
             "-   *)    (A0*)\n"
             "chase (V*V) *\n"
             "-   (A1*  (V*V)\n"
             "-   *)    (A1*)\n"
             "\n")
    # normalize: real props use (V*) for the verb; build faithful streams
    props = ("-\t(A0*\t*\n"
             "-\t*)\t(A0*)\n"
             "chase\t(V*)\t*\n"
             "see\t(A1*\t(V*)\n"
             "-\t*)\t(A1*)\n"
             "\n")
    for name, text in (("words", words), ("props", props)):
        buf = io.BytesIO()
        with gzip.GzipFile(fileobj=buf, mode="w") as g:
            g.write(text.encode())
        setattr(test_conll05_parses_props_brackets, name, buf.getvalue())
    with tarfile.open(d / "conll05st-tests.tar.gz", "w:gz") as tar:
        _tar_bytes(tar, "conll05st-release/test.wsj/words/"
                   "test.wsj.words.gz",
                   test_conll05_parses_props_brackets.words)
        _tar_bytes(tar, "conll05st-release/test.wsj/props/"
                   "test.wsj.props.gz",
                   test_conll05_parses_props_brackets.props)
    (d / "wordDict.txt").write_text(
        "The\ncat\nchased\na\ndog\nbos\neos\n")
    (d / "verbDict.txt").write_text("chase\nsee\n")
    (d / "targetDict.txt").write_text("B-A0\nI-A0\nB-A1\nI-A1\nB-V\nO\n")
    samples = list(ds.conll05.test()())
    assert len(samples) == 2                       # one per predicate
    word_d, verb_d, label_d = ds.conll05.get_dict()
    words_ids, pred, ctx_n2, ctx_n1, ctx_0, ctx_p1, ctx_p2, mark, \
        labels = samples[0]
    assert words_ids == [word_d[w] for w in
                         ("The", "cat", "chased", "a", "dog")]
    assert pred == [verb_d["chase"]] * 5
    assert ctx_0 == [word_d["chased"]] * 5         # the B-V word
    assert mark == [1, 1, 1, 1, 1]                 # v-2..v+2 window
    # first predicate: The..cat = A0 (B,I), chased = V, a..dog = A1 (B,I)
    assert labels == [label_d["B-A0"], label_d["I-A0"], label_d["B-V"],
                      label_d["B-A1"], label_d["I-A1"]]
    assert label_d["O"] == max(label_d.values())


# --- sentiment ---------------------------------------------------------------

def test_sentiment_parses_movie_reviews_dir(data_home):
    base = data_home / "sentiment" / "corpora" / "movie_reviews"
    (base / "neg").mkdir(parents=True)
    (base / "pos").mkdir(parents=True)
    (base / "neg" / "cv000.txt").write_text("bad bad plot .")
    (base / "pos" / "cv000.txt").write_text("good good good film !")
    wd = dict(ds.sentiment.get_word_dict())
    assert wd["good"] == 0 and wd["bad"] == 1      # freq-sorted
    samples = list(ds.sentiment.train()())
    assert len(samples) == 2                       # interleaved neg, pos
    assert samples[0][1] == 0 and samples[1][1] == 1
    assert samples[1][0].count(wd["good"]) == 3
    assert wd["."] in samples[0][0]                # punctuation tokenized


# --- wmt14 -------------------------------------------------------------------

def test_wmt14_parses_tarball(data_home):
    d = data_home / "wmt14"
    d.mkdir()
    src_dict = "<s>\n<e>\n<unk>\nles\nchats\n"
    trg_dict = "<s>\n<e>\n<unk>\nthe\ncats\n"
    train = "les chats\tthe cats\nles " + "x " * 100 + "\tthe\n"
    test = "les\tthe\n"
    with tarfile.open(d / "wmt14.tgz", "w:gz") as tar:
        _tar_bytes(tar, "wmt14/src.dict", src_dict.encode())
        _tar_bytes(tar, "wmt14/trg.dict", trg_dict.encode())
        _tar_bytes(tar, "wmt14/train/train", train.encode())
        _tar_bytes(tar, "wmt14/test/test", test.encode())
    got = list(ds.wmt14.train(dict_size=5)())
    assert len(got) == 1                           # >80-token pair dropped
    src_ids, trg_ids, trg_next = got[0]
    assert src_ids == [0, 3, 4, 1]                 # <s> les chats <e>
    assert trg_ids == [0, 3, 4]                    # <s> the cats
    assert trg_next == [3, 4, 1]                   # the cats <e>
    sd, td = ds.wmt14.get_dict(5)
    assert sd["chats"] == 4 and td["cats"] == 4
    rsd, _ = ds.wmt14.get_dict(5, reverse=True)
    assert rsd[4] == "chats"


# --- wmt16 -------------------------------------------------------------------

def test_wmt16_builds_dicts_and_parses(data_home):
    d = data_home / "wmt16"
    d.mkdir()
    train = ("two men\tzwei manner\n"
             "two dogs\tzwei hunde\n")
    val = "two men\tzwei manner\n"
    with tarfile.open(d / "wmt16.tar.gz", "w:gz") as tar:
        _tar_bytes(tar, "wmt16/train", train.encode())
        _tar_bytes(tar, "wmt16/val", val.encode())
        _tar_bytes(tar, "wmt16/test", val.encode())
    got = list(ds.wmt16.train(src_dict_size=6, trg_dict_size=6)())
    assert len(got) == 2
    src_ids, trg_ids, trg_next = got[0]
    en = ds.wmt16.get_dict("en", 6)
    de = ds.wmt16.get_dict("de", 6)
    assert en["<s>"] == 0 and en["<e>"] == 1 and en["<unk>"] == 2
    assert src_ids == [0, en["two"], en["men"], 1]
    assert trg_ids == [0, de["zwei"], de["manner"]]
    assert trg_next == [de["zwei"], de["manner"], 1]
    # dict files are cached on disk like the reference
    assert (d / "en_6.dict").exists()
    # de as source flips the columns
    got_de = list(ds.wmt16.train(6, 6, src_lang="de")())
    assert got_de[0][0][1] == ds.wmt16.get_dict("de", 6)["zwei"]
    with pytest.raises(ValueError):
        ds.wmt16.train(6, 6, src_lang="fr")


# --- flowers -----------------------------------------------------------------

def test_flowers_parses_tgz_and_mats(data_home):
    from PIL import Image
    import scipy.io as scio

    d = data_home / "flowers"
    d.mkdir()
    rng = np.random.RandomState(0)
    with tarfile.open(d / "102flowers.tgz", "w:gz") as tar:
        for i in (1, 2):
            img = Image.fromarray(
                rng.randint(0, 256, (300, 280, 3)).astype(np.uint8))
            buf = io.BytesIO()
            img.save(buf, format="JPEG")
            _tar_bytes(tar, f"jpg/image_{i:05d}.jpg", buf.getvalue())
    scio.savemat(d / "imagelabels.mat",
                 {"labels": np.array([[5, 102]], np.uint8)})
    scio.savemat(d / "setid.mat",
                 {"trnid": np.array([[1]], np.uint16),
                  "tstid": np.array([[2]], np.uint16),
                  "valid": np.array([[2]], np.uint16)})
    got = list(ds.flowers.train()())
    assert len(got) == 1
    img, label = got[0]
    assert img.shape == (3 * 224 * 224,) and img.dtype == np.float32
    assert 0.0 <= img.min() and img.max() <= 1.0
    assert label == 4                              # 1-based 5 -> 0-based 4
    assert [lab for _, lab in ds.flowers.test()()] == [101]


# --- voc2012 -----------------------------------------------------------------

def test_voc2012_parses_voc_tar(data_home):
    from PIL import Image

    d = data_home / "voc2012"
    d.mkdir()
    rng = np.random.RandomState(0)
    jpg = Image.fromarray(rng.randint(0, 256, (48, 64, 3)).astype(np.uint8))
    jpg_buf = io.BytesIO()
    jpg.save(jpg_buf, format="JPEG")
    mask = np.zeros((48, 64), np.uint8)
    mask[10:20, 10:30] = 15                        # class 15 region
    png = Image.fromarray(mask, mode="P")
    png.putpalette([0] * 768)
    png_buf = io.BytesIO()
    png.save(png_buf, format="PNG")
    with tarfile.open(d / "VOCtrainval_11-May-2012.tar", "w") as tar:
        _tar_bytes(tar, "VOCdevkit/VOC2012/ImageSets/Segmentation/"
                   "trainval.txt", b"2007_000001\n")
        _tar_bytes(tar, "VOCdevkit/VOC2012/JPEGImages/2007_000001.jpg",
                   jpg_buf.getvalue())
        _tar_bytes(tar, "VOCdevkit/VOC2012/SegmentationClass/"
                   "2007_000001.png", png_buf.getvalue())
    got = list(ds.voc2012.train()())
    assert len(got) == 1
    img, seg = got[0]
    assert img.shape == (3, 48, 64) and img.dtype == np.float32
    assert seg.shape == (48, 64) and seg.dtype == np.int32
    assert set(np.unique(seg)) == {0, 15}


# --- fallback ----------------------------------------------------------------

def test_all_modules_fall_back_to_synthetic(data_home):
    """With an empty DATA_HOME every module still serves schema-correct
    synthetic data — the zero-egress default."""
    next(ds.cifar.train10()())
    next(ds.imdb.train()())
    next(ds.imikolov.train(n=3)())
    next(ds.movielens.train()())
    next(ds.conll05.test()())
    next(ds.sentiment.train()())
    next(ds.wmt14.train(30)())
    next(ds.wmt16.train(30, 30)())
    next(ds.flowers.train()())
    next(ds.voc2012.train()())
    next(ds.mnist.train()())
    next(ds.uci_housing.train()())
    sample = next(ds.mq2007.train()())
    assert sample is not None
